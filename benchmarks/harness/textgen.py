"""Every input of a run, made from ``--seed``: failures to store (and the
traces they came from), prompts to warn about and to chat with.

The same seed gives the same inputs. A seed changes WHICH words (and, in
``weights``, which weights), never how much work or when: every seed sends the
same lengths at the same due times (``arrivals/poisson.py``).
"""

from __future__ import annotations

import random

_CONS = "bcdfghjklmnprstvwz"
_VOW = "aeiou"
_TOOLS = ("search", "sql", "browser", "python", "calculator", "retriever")
_ENV_KEYS = ("os", "region", "tier", "gpu", "locale")
_CITE_TAILS = (
    "and include citations even if not provided",
    "and include references even if none are given",
    "and include citations for every claim",
)
_VERBS = ("Summarize", "Explain")


_SALTS = {s: k for k, s in enumerate(("vocab", "head", "ctx", "body", "resp", "len", "warn", "chat", "order", "sample"))}


def rng_for(seed: int, salt: str, i=0) -> random.Random:
    """An independent stream for (seed, salt, i). Integer seeding: a string
    seed costs a hash for each of the hundreds of thousands of rows."""
    if not isinstance(i, int):
        i = sum(ord(c) << (8 * k) for k, c in enumerate(str(i)))
    return random.Random(((seed * 64 + _SALTS[salt]) << 40) + i)


def vocabulary(seed: int, n: int = 4096) -> list:
    rng = rng_for(seed, "vocab")
    words = set()
    while len(words) < n:
        k = rng.choice((2, 3, 3, 4))
        words.add("".join(rng.choice(_CONS) + rng.choice(_VOW) for _ in range(k)) + rng.choice(("", "n", "s", "r")))
    return sorted(words)


class Corpus:
    """Failure-bearing prompts, numbered: prompt ``i`` is a pure function of
    (seed, i), its first 80 characters are distinct from every other's, and it
    asks for citations, so the rule classifier flags a trace that carries it
    with a citation-bearing response."""

    def __init__(self, seed: int):
        self.seed = seed
        self.vocab = vocabulary(seed)

    def head_words(self, i: int, n: int = 9) -> list:
        rng = rng_for(self.seed, "head", i)
        return [rng.choice(self.vocab) for _ in range(n)]

    def context(self, i: int) -> tuple:
        rng = rng_for(self.seed, "ctx", i)
        tools = sorted(rng.sample(_TOOLS, rng.randrange(0, 3)))
        env = {k: "x" for k in sorted(rng.sample(_ENV_KEYS, rng.randrange(1, 3)))}
        return tools, env

    @staticmethod
    def stored_length(i: int) -> int:
        """Stored failure ``i`` came from a prompt of 200-800 characters."""
        return 200 + (i * 37) % 601

    def stored_item(self, i: int) -> tuple:
        """(prompt, tools, env keys) of stored failure ``i``."""
        tools, env = self.context(i)
        return self.prompt(i, self.stored_length(i)), tools, sorted(env)

    def prompt(self, i: int, length: int, head: list | None = None) -> str:
        """``head`` replaces the words of the first 80 characters (a
        near-duplicate changes one of them)."""
        rng = rng_for(self.seed, "body", i)
        words = head if head is not None else self.head_words(i)
        verb = _VERBS[i % 2]
        text = f"{verb} report {i:07d} {' '.join(words)}"
        tail = " " + _CITE_TAILS[i % len(_CITE_TAILS)] + "."
        need = length - len(text) - len(tail)
        if need > 0:
            filler = " " + " ".join(rng.choices(self.vocab, k=need // 5 + 1))
            text += filler[:need].rstrip()
        return text + tail

    def trace(self, i: int, length: int, *, apps: int = 2) -> dict:
        """The trace that stored failure ``i`` came from: its prompt with a
        response that cites sources no one gave."""
        tools, env = self.context(i)
        rng = rng_for(self.seed, "resp", i)
        response = (f"Here is the answer.\n\nReferences:\n[1] Smith et al. ({2000 + rng.randrange(24)}) "
                    f"A Study.\n[2] Doe (2021) Another.")
        return {
            "trace_id": f"b-{self.seed}-{i:07d}", "ts": 1_700_000_000 + i, "app_id": f"app-{i % apps}",
            "prompt": self.prompt(i, length), "response": response, "tools": tools, "env": env,
        }


def lengths_for(order_seed: int, n: int, lo: int, hi: int, salt: str) -> list:
    """``n`` lengths evenly spread over [lo, hi], in an order drawn from
    ``order_seed`` (the traffic file's ``gaps_seed``, not the run's seed: which
    request is long decides which admit bucket meets which burst)."""
    base = [lo + (hi - lo) * k // max(1, n - 1) for k in range(n)]
    rng_for(order_seed, "len", salt).shuffle(base)
    return base


def pick_app(rng: random.Random, apps: int, hot_share: float) -> str:
    """``hot_share`` of the traffic lands on app-0 (the repo's hot_key_skew)."""
    if hot_share > 0.0 and rng.random() < hot_share:
        return "app-0"
    return f"app-{rng.randrange(1, max(2, apps))}"


def warn_request(corpus: Corpus, seed: int, j: int, kind: str, stored: int, length: int, app: str) -> dict:
    """Request ``j`` of a warn stream. ``kind``:
    near    — a stored failure with one word of its first 80 characters changed
    intent  — asks for citations in words no stored failure used
    other   — asks for no citations at all
    """
    rng = rng_for(seed, "warn", j)
    if kind == "near":
        i = rng.randrange(stored)
        head = corpus.head_words(i)
        head[rng.randrange(0, 4)] = rng.choice(corpus.vocab)  # inside the 80-character hint
        tools, env = corpus.context(i)
        prompt = corpus.prompt(i, length, head=head)
    else:
        i = 10_000_000 + j
        tools, env = corpus.context(i)
        prompt = corpus.prompt(i, length)
        if kind == "other":
            for tail in _CITE_TAILS:
                prompt = prompt.replace(" " + tail, " and keep it short")
    return {"app_id": app, "prompt": prompt, "tools": tools, "env": env}


def chat_prompt(corpus: Corpus, seed: int, j: int, nbytes: int) -> str:
    """ASCII prompt of exactly ``nbytes`` bytes, distinct for each ``j``."""
    rng = rng_for(seed, "chat", j)
    text = f"Q{j:06d} why did {rng.choice(corpus.vocab)}"
    while len(text) < nbytes:
        text += " " + rng.choice(corpus.vocab)
    return text[:nbytes - 1].rstrip() .ljust(nbytes - 1, "x") + "?"
