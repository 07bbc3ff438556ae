"""Which device a binary runs on — chosen, checked and logged in one
place — and where its compiled programs are cached.

Both are process-wide JAX settings that must be made before the first
backend touch, so the learner, actor and serve `main()`s call these
first; `chip_smoke.py`, `bench.py` and `tests/conftest.py` share the
cache rule so that every process of one run finds the others' programs.
"""

from __future__ import annotations

import dataclasses
import logging
import os

import jax

_log = logging.getLogger(__name__)

# The directory is part of what makes a cache entry findable, so it is
# fixed: inside the checkout (git-ignored), no uid, pid or time in it.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


@dataclasses.dataclass
class CompileCache:
    """Where this process caches compiled programs, and its traffic so
    far as JAX's own monitoring events count it: `hits` are programs
    read back, `misses` programs compiled here and written."""

    dir: str
    hits: int = 0
    misses: int = 0

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def use_compile_cache() -> CompileCache:
    """Turn on JAX's persistent compilation cache. Where
    JAX_COMPILATION_CACHE_DIR is set JAX reads it and no directory is
    set in code (the caller's choice wins, and child processes inherit
    it); otherwise the cache lives at COMPILE_CACHE_DIR."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    cache = CompileCache(path)
    jax.monitoring.register_event_listener(cache._on_event)
    return cache


def init_devices(platform: str, role: str):
    """Initialise the backend this binary was asked for and say what it
    got. `platform` is the binary's --platform flag: "" takes JAX's
    default backend (JAX_PLATFORMS, else the best one present), a name
    pins that backend — and a pinned backend that is not there is an
    error here, never a run on another device."""
    if platform:
        jax.config.update("jax_platforms", platform)
    devices = jax.devices()
    first = devices[0]
    if platform and first.platform != platform.split(",")[0]:
        raise RuntimeError(
            f"--platform {platform} asked for, but jax.devices() are {first.platform!r}"
        )
    _log.info(
        "%s up: platform=%s device_kind=%s devices=%d",
        role,
        first.platform,
        first.device_kind,
        len(devices),
    )
    return devices
