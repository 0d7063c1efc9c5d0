"""The plain reference of the failure index: signature, hashed n-gram
embedding, rule classifier and exact top-k, written from the description in
``kakveda_tpu/ops/featurizer.py`` and ``core/fingerprint.py`` and importing
nothing of the program. float32 throughout, one dense matrix, no kernels.

Signature text:  intent_tags:<sorted tags> | prompt_hint:<first 80 chars of the
lower-cased, whitespace-collapsed prompt> | tools:<sorted> | env_keys:<sorted>.
Embedding: signed feature hashing (crc32; bucket = low bits, sign = bit 31) of
word 1- and 2-grams of the hint (weight 1), whole tags (3), whole tools (1),
whole env keys (0.25); L2-normalised. Score: dot product, held to [-1, 1]. The
rows are computed in float32; ``stated`` rounds them to the type the
configuration states before they are multiplied.
"""

from __future__ import annotations

import re
import zlib

import numpy as np

_WS = re.compile(r"\s+")
_TOK = re.compile(r"[a-z0-9_]+")
_CITE = ("citation", "citations", "reference", "references", "sources", "bibliography")
_SUMM = ("summarize", "summary", "tl;dr")
_EXPL = ("explain", "explanation", "describe")
_MARKERS = (re.compile(r"\[[0-9]+\]"), re.compile(r"\([A-Za-z]+,\s*\d{4}\)"), re.compile(r"doi:\s*\S+"))
_FIELDS = (("intent_tags", 3.0, True), ("prompt_hint", 1.0, False), ("tools", 1.0, True), ("env_keys", 0.25, True))


def normalize(prompt: str) -> str:
    return _WS.sub(" ", prompt.strip().lower())


def intent_tags(prompt: str) -> list:
    p = normalize(prompt)
    tags = set()
    cites = any(k in p for k in _CITE)
    if cites:
        tags.add("intent:citations_required")
    if any(k in p for k in _SUMM):
        tags.add("task:summarization")
    if any(k in p for k in _EXPL):
        tags.add("task:explanation")
    if "even if not provided" in p or "even if none" in p:
        tags.add("constraint:no_sources_provided")
    if "include" in p and cites:
        tags.add("instruction:include_references")
    return sorted(tags)


def is_failure(prompt: str, response: str) -> bool:
    """The rule classifier: the prompt asks for citations and the response
    looks like it has some."""
    if "intent:citations_required" not in intent_tags(prompt):
        return False
    if any(rx.search(response) for rx in _MARKERS):
        return True
    low = response.lower()
    return "references" in low or "bibliography" in low


def signature_fields(prompt: str, tools, env_keys) -> tuple:
    return (intent_tags(prompt), normalize(prompt)[:80], sorted(set(tools)), sorted(env_keys))


def signature_text(prompt: str, tools, env_keys) -> str:
    tags, hint, tl, ek = signature_fields(prompt, tools, env_keys)
    return f"intent_tags:{','.join(tags)} | prompt_hint:{hint} | tools:{','.join(tl)} | env_keys:{','.join(ek)}"


def features(prompt: str, tools, env_keys) -> list:
    """(term, weight) pairs of one execution's signature."""
    tags, hint, tl, ek = signature_fields(prompt, tools, env_keys)
    feats = [(f"intent_tags={t}", 3.0) for t in tags]
    words = _TOK.findall(hint)
    feats += [(w, 1.0) for w in words]
    feats += [(f"{a} {b}", 1.0) for a, b in zip(words, words[1:])]
    feats += [(f"tools={t}", 1.0) for t in tl]
    feats += [(f"env_keys={k}", 0.25) for k in ek]
    return feats


def embed_sparse(items, dim: int = 2048, width: int = 96) -> tuple:
    """(idx [n, width] int32, val [n, width] float32): each row's non-zero
    buckets and their L2-normalised values, padded with (0, 0.0). A signature
    touches a few dozen of the ``dim`` buckets."""
    idx = np.zeros((len(items), width), np.int32)
    val = np.zeros((len(items), width), np.float32)
    mask = dim - 1
    crc = zlib.crc32
    for r, (prompt, tools, env_keys) in enumerate(items):
        row = {}
        for term, w in features(prompt, tools, env_keys):
            h = crc(term.encode())
            b = h & 0x7FFFFFFF & mask
            row[b] = row.get(b, 0.0) + (w if (h >> 31) & 1 == 0 else -w)
        if len(row) > width:
            raise ValueError(f"signature with {len(row)} features; raise width")
        v = np.fromiter(row.values(), np.float32, len(row))
        ss = float(np.sum(v.astype(np.float64) ** 2))
        idx[r, :len(row)] = np.fromiter(row.keys(), np.int32, len(row))
        # as the featurizer is described: squares summed in float64, the
        # reciprocal root rounded to float32, each value multiplied by it in
        # float32. Dividing by the norm differs in the last float32 bit now
        # and then, and near a bf16 midpoint that bit flips the stored value:
        # one seed in fifteen read 0.0003 where the others read 0.0 (PERF.md).
        val[r, :len(row)] = v * np.float32(1.0 / np.sqrt(ss)) if ss > 0 else v
    return idx, val


def densify(idx: np.ndarray, val: np.ndarray, dim: int = 2048) -> np.ndarray:
    out = np.zeros((idx.shape[0], dim), np.float32)
    np.add.at(out, (np.arange(idx.shape[0])[:, None], idx), val)
    return out


def embed(items, dim: int = 2048) -> np.ndarray:
    """[n, dim] float32, rows L2-normalised. ``items``: (prompt, tools, env_keys)."""
    return densify(*embed_sparse(items, dim), dim)


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (the type the
    configuration states for the rows), kept in float32."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def stated(x: np.ndarray, row_bytes: int) -> np.ndarray:
    """``x`` in the type the configuration states for rows and queries:
    bf16 where a row element has 2 bytes, float32 where it has 4."""
    return round_bf16(x) if row_bytes == 2 else x


def quantize_rows_int8(rows: np.ndarray) -> np.ndarray:
    """The control's precision: symmetric int8 with one scale per row,
    the nearest step below the bf16 rows the configuration states."""
    scale = np.abs(rows).max(axis=1, keepdims=True) / 127.0
    scale[scale == 0] = 1.0
    return (np.rint(rows / scale).clip(-127, 127) * scale).astype(np.float32)


def cosine(dots: np.ndarray) -> np.ndarray:
    """A score is a cosine: the dot product of two unit rows, held to [-1, 1].
    In bf16 a unit row's product with itself rounds to as much as 1.004; the
    index hands that out as 1.0, and so does the reference."""
    return np.clip(dots, -1.0, 1.0)


def scores(queries: np.ndarray, rows: np.ndarray, block: int = 65536) -> np.ndarray:
    """[q, n] float32 cosines, in blocks of rows."""
    out = np.empty((queries.shape[0], rows.shape[0]), np.float32)
    for s in range(0, rows.shape[0], block):
        out[:, s:s + block] = cosine(queries @ rows[s:s + block].T)
    return out


def _embed_stored(args) -> tuple:
    seed, start, stop, dim = args
    from . import textgen

    corpus = textgen.Corpus(seed)
    return embed_sparse([corpus.stored_item(i) for i in range(start, stop)], dim)


def embed_stored(seed: int, n: int, dim: int = 2048, row_bytes: int = 4, workers: int = 8) -> np.ndarray:
    """The rows of stored failures 0..n-1, in the type the configuration
    states (``stated``). Worker processes each regenerate their share from the
    seed and hand back the sparse pairs; the values are rounded before they
    are laid out densely (a row's buckets are distinct, so that is the same
    as rounding the dense row, at a twentieth of the elements)."""
    if n == 0:
        return np.zeros((0, dim), np.float32)
    import multiprocessing as mp

    step = max(2048, -(-n // (workers * 4)))
    jobs = [(seed, s, min(n, s + step), dim) for s in range(0, n, step)]
    if len(jobs) == 1:
        parts = [_embed_stored(jobs[0])]
    else:
        with mp.get_context("spawn").Pool(min(workers, len(jobs))) as pool:
            parts = pool.map(_embed_stored, jobs)
    return densify(np.concatenate([p[0] for p in parts]), stated(np.concatenate([p[1] for p in parts]), row_bytes), dim)
