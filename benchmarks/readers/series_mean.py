"""Mean of a /metrics histogram's observations inside the window (sum and
count deltas, never bucket quantiles), times ``scale``."""

from harness import prom


def read(ctx, params):
    v = prom.mean_delta(ctx["prom_before"], ctx["prom_after"], params["family"], **params.get("labels", {}))
    return None if v is None else v * params.get("scale", 1.0)
