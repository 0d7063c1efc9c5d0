"""File-backed config with hot reload on mtime change.

Capability parity with the reference's ConfigStore
(reference: services/shared/config.py:18-58): YAML file, reload when the
file's mtime changes, per-service instances with no shared mutable state.
Adds typed accessors for the knobs every subsystem reads
(reference: config/config.yaml:1-20).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Mapping, Optional, Tuple

import yaml

DEFAULT_CONFIG: Dict[str, Any] = {
    "failure_matching": {
        "similarity_threshold": 0.8,
        "mode": "semantic_plus_rule",
        "embedding_dim": 2048,
        "top_k": 5,
    },
    "warning_policy": {"default_action": "warn"},
    "health_score": {
        "severity_weights": {"low": 1, "medium": 3, "high": 7},
        "window_size": 10,
        "base_score": 100,
    },
    "sampling": {"enabled": False},
    "hot_reload": {"enabled": True, "poll_seconds": 2},
}


@dataclass(frozen=True)
class HotReloadConfig:
    enabled: bool
    poll_seconds: int


def _section_of(data: Mapping[str, Any], name: str) -> Mapping[str, Any]:
    return data.get(name) or DEFAULT_CONFIG.get(name) or {}


class ConfigStore:
    """YAML config, hot-reloaded when the file's mtime changes.

    ``get()`` makes one ``stat`` call and re-parses only when the mtime
    differs from the one it last parsed at, so an edit takes effect on the
    very next read. ``hot_reload.poll_seconds`` is parsed and never
    consulted: no read is served from a cache older than the file.
    """

    def __init__(self, config_path: Optional[str | Path] = None):
        default = os.environ.get("KAKVEDA_CONFIG_PATH", "config/config.yaml")
        self._path = Path(config_path or default)
        self._last_mtime: Optional[float] = None
        self._cache: Dict[str, Any] = {}
        self._loaded = False

    @property
    def path(self) -> Path:
        return self._path

    def _read(self) -> Dict[str, Any]:
        if not self._path.exists():
            return {}
        with self._path.open("r", encoding="utf-8") as f:
            return yaml.safe_load(f) or {}

    def get(self) -> Dict[str, Any]:
        """Current config; re-parses only on first use or mtime change.

        One ``stat`` syscall a call (a missing or unreadable file reads as
        mtime ``None``, i.e. the defaults); the mtime check is what detects
        edits, so there is no parse-every-poll churn.
        """
        try:
            mtime = os.stat(self._path).st_mtime
        except OSError:
            mtime = None

        if not self._loaded or (mtime != self._last_mtime and self.hot_reload().enabled):
            self._cache = self._read()
            self._last_mtime = mtime
            self._loaded = True
        return self._cache

    def hot_reload(self) -> HotReloadConfig:
        data = self._cache if self._loaded else (self._read() or {})
        hr = data.get("hot_reload") or {}
        return HotReloadConfig(
            enabled=bool(hr.get("enabled", True)),
            poll_seconds=int(hr.get("poll_seconds", 2)),
        )

    # --- typed accessors -------------------------------------------------

    def _section(self, name: str) -> Mapping[str, Any]:
        return _section_of(self.get(), name)

    def verdict_inputs(self) -> Tuple[float, str]:
        """(similarity threshold, default action) from ONE ``get()``: what a
        warn batch judges by, for one ``stat`` of the file."""
        data = self.get()
        return (
            float(_section_of(data, "failure_matching").get("similarity_threshold", 0.8)),
            str(_section_of(data, "warning_policy").get("default_action", "warn")),
        )

    def similarity_threshold(self) -> float:
        return self.verdict_inputs()[0]

    def match_top_k(self) -> int:
        sect = self._section("failure_matching")
        return int(sect.get("top_k", 5))

    def embedding_dim(self) -> int:
        sect = self._section("failure_matching")
        return int(sect.get("embedding_dim", 2048))

    def default_action(self) -> str:
        return self.verdict_inputs()[1]

    def severity_weights(self) -> Dict[str, float]:
        sect = self._section("health_score")
        w = sect.get("severity_weights") or {"low": 1, "medium": 3, "high": 7}
        return {k: float(v) for k, v in w.items()}

    def base_score(self) -> float:
        sect = self._section("health_score")
        return float(sect.get("base_score", 100))


def write_default_config(path: str | Path) -> Path:
    """Materialize the default config file (used by `kakveda-tpu init`)."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(yaml.safe_dump(DEFAULT_CONFIG, sort_keys=False), encoding="utf-8")
    return p
