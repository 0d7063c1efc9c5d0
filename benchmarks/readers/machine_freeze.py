"""What the canary saw inside the window (``harness/machine.py``): ``stat``
``sum`` (ms of the window in which every process of the machine was held) or
``max`` (the longest such gap). 0 where it saw none; nothing where no canary
ran."""


def read(ctx, params):
    m = ctx.get("machine")
    if not m:
        return None
    return m[{"sum": "machine_freeze_ms", "max": "machine_freeze_max_ms"}[params.get("stat", "sum")]]
