"""Persistent XLA compilation cache wiring.

At survey scale a fresh process pays minutes of XLA compiles; the
persistent cache amortises them across processes. Every entry point
(the CLIs via apply_platform_env, bench.py, chip_smoke.py, the test
suite's conftest) calls :func:`enable_compilation_cache` before building
programs.

Where the cache lives is the caller's choice: ``JAX_COMPILATION_CACHE_DIR``
when it is set, otherwise one fixed directory inside the checkout
(``<repo>/.jax_cache``, git-ignored). The path is part of every entry's
key, so it is never built from a temp name, a pid or the time."""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def default_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable_compilation_cache() -> str | None:
    """Point jax at the persistent on-disk compilation cache and return
    its path (None when it could not be enabled). With
    ``JAX_COMPILATION_CACHE_DIR`` set, that directory is the one jax
    uses; the call only re-reads it, in case it changed after jax was
    imported. Safe to call repeatedly, before or after backend init;
    failures are non-fatal (an uncached run is just slower)."""
    cache = default_cache_dir()
    try:
        os.makedirs(cache, exist_ok=True)
        import jax

        jax.config.update("jax_compilation_cache_dir", cache)
        # cache everything (default floor would skip fast compiles),
        # unless the operator set their own floor via the env var
        if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0
            )
        return cache
    except Exception:  # read-only checkout etc.: run without the cache
        return None


def cache_entry_paths(cache_dir: str | None = None) -> list[str]:
    """The persistent cache's entry files (quarantined ``*.corrupt``
    forensics excluded). Empty when the cache dir is absent."""
    d = cache_dir or default_cache_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return []
    return sorted(
        p
        for n in names
        if not n.endswith(".corrupt")
        for p in (os.path.join(d, n),)
        if os.path.isfile(p)
    )


def quarantine_cache_entries(cache_dir: str | None = None) -> list[str]:
    """Move every persistent-cache entry aside to ``*.corrupt`` (rename,
    never delete — the torn bytes are the post-mortem) so the next
    compile repopulates the cache from scratch instead of crashing on a
    garbled deserialisation. The cache is a pure optimisation: losing
    all of it costs recompiles, never correctness — which is why a
    single suspect entry quarantines the lot (XLA's entry filenames are
    opaque hashes; the damaged one cannot be singled out from outside).
    Returns the quarantine paths."""
    from ..resilience import STATS, quarantine_artifact

    out = []
    entries = cache_entry_paths(cache_dir)
    for path in entries:
        q = quarantine_artifact(path)
        if q:
            out.append(q)
    if entries:
        STATS.corrupt_artifact("xla cache")
        try:
            from ..obs.telemetry import current

            current().event(
                "corrupt_artifact", artifact="xla cache",
                path=cache_dir or default_cache_dir(),
                quarantined_to=f"{len(out)} entries",
            )
        except Exception:
            pass  # telemetry must never mask the recovery itself
    return out
