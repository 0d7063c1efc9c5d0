"""The timed path, broken underneath, for ``benchmarks/tests`` alone.

Each fault alters an answer where the program produces it, below everything
the harness drives, so that a test can see ``correct`` come out false through
the same run that decides it on the chip. No run of the benchmark takes one:
``run.py --fault`` is refused without ``--rehearse-on-cpu``.

warn_answer   GFKB.match_batch_info hands back each query's matches without the best one
chat_token    ServingEngine._emit gives every request's third token as the next id up
"""

from __future__ import annotations

NAMES = ("warn_answer", "chat_token")


def plant(name: str) -> None:
    if name == "warn_answer":
        from kakveda_tpu.index.gfkb import GFKB

        inner = GFKB.match_batch_info

        def match_batch_info(self, *a, **kw):
            matches, info = inner(self, *a, **kw)
            return [m[1:] for m in matches], info

        GFKB.match_batch_info = match_batch_info
    elif name == "chat_token":
        from kakveda_tpu.models.serving import ContinuousBatcher

        inner_emit = ContinuousBatcher._emit

        def _emit(self, slot, st, tok_row, finished):
            row = [int(t) for t in tok_row]
            if len(st.out) <= 2 < len(st.out) + len(row):
                k = 2 - len(st.out)
                row[k] = 3 + (row[k] - 3 + 1) % 256
            return inner_emit(self, slot, st, row, finished)

        ContinuousBatcher._emit = _emit
    else:
        raise ValueError(f"unknown fault {name!r} (has: {NAMES})")
