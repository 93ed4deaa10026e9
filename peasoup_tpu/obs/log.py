"""Structured library logging.

The library never prints to stdout: every informational or warning message
goes through a child of the ``peasoup_tpu`` logger (ruff rule T201
enforces this — see pyproject.toml). Importing the package installs a
``NullHandler`` only, so embedded users are silent by default and wire
the logger however their application does; the CLI entry points call
:func:`configure` with the level resolved from ``-v`` / ``--log-level``
(``resolve_level``), which installs a single stderr handler.

Messages always go to **stderr**: stdout is reserved for
machine-readable output (piped candidate lists, report renders), the
same contract as the progress bar (utils/progress.py).
"""

from __future__ import annotations

import logging
import os
import sys

ROOT_LOGGER = "peasoup_tpu"

_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
}


class _StderrHandler(logging.StreamHandler):
    """A stream handler that, unless given a stream, writes to whatever
    ``sys.stderr`` is when it writes: a stderr replaced after
    configuration (a caller's capture that is later closed) is never
    written after it goes away."""

    def __init__(self, stream=None):
        logging.Handler.__init__(self)
        self._stream = stream

    @property
    def stream(self):
        return self._stream if self._stream is not None else sys.stderr

    @stream.setter
    def stream(self, value):
        self._stream = value


# one library-owned handler, reused across configure() calls so repeated
# CLI invocations in one process (tests) never stack duplicate handlers
_handler: logging.StreamHandler | None = None

logging.getLogger(ROOT_LOGGER).addHandler(logging.NullHandler())


def get_logger(name: str | None = None) -> logging.Logger:
    """The library logger, or a dotted child (``get_logger("pipeline")``
    -> ``peasoup_tpu.pipeline``)."""
    return logging.getLogger(
        ROOT_LOGGER if not name else f"{ROOT_LOGGER}.{name}"
    )


def resolve_level(
    log_level: str | int | None, verbose: bool = False
) -> int:
    """Level precedence: explicit ``--log-level`` > ``-v`` (INFO) >
    PEASOUP_LOG_LEVEL env > WARNING."""
    if log_level is None:
        log_level = (
            "info" if verbose else os.environ.get("PEASOUP_LOG_LEVEL")
        )
    if log_level is None:
        return logging.WARNING
    if isinstance(log_level, int):
        return log_level
    try:
        return _LEVELS[str(log_level).strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown log level {log_level!r}; "
            f"expected one of {sorted(_LEVELS)}"
        ) from None


def configure(
    level: str | int | None = None,
    verbose: bool = False,
    stream=None,
) -> logging.Logger:
    """Install (or retune) the stderr handler on the library logger and
    set its threshold. Idempotent: calling again adjusts the level and
    stream on the existing handler instead of stacking a new one."""
    global _handler
    logger = get_logger()
    resolved = resolve_level(level, verbose)
    if _handler is None:
        _handler = _StderrHandler(stream)
        _handler.setFormatter(
            logging.Formatter("[%(levelname)s] %(name)s: %(message)s")
        )
        logger.addHandler(_handler)
    elif stream is not None:
        _handler.setStream(stream)
    logger.setLevel(resolved)
    return logger
