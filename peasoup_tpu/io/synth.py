"""Synthetic filterbanks with an injected pulsar, made from a seed.

The test input where no observation is at hand (``/root/reference`` is
absent on most machines): 2-bit noise uniform in 0..2 with a dispersed
square pulse train adding 1 (with a chosen probability) in its
on-phase, written block by block so a
survey-size beam (1024 channels x 2^21 samples) never holds more than
one block of samples beside the packed file.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

from ..plan.dm_plan import delay_table
from .sigproc import SigprocHeader, pack_bits, write_sigproc_header


def write_pulsar_fil(
    path: str,
    *,
    nchans: int,
    nsamps: int,
    tsamp: float,
    fch1: float,
    foff: float,
    period: float,
    dm: float,
    seed: int,
    duty: float = 0.06,
    amp: float = 1.0,
    block: int = 8192,
) -> str:
    """Write a 2-bit filterbank holding a pulsar of ``period`` seconds at
    ``dm`` (delays rounded like the search's own delay table), published
    atomically at ``path``. Each on-pulse sample gains 1 with
    probability ``amp``, so a weak pulsar stays a few-sigma signal per
    channel. Returns ``path``."""
    if nchans % 4:
        raise ValueError("2-bit rows pack into whole bytes only when "
                         "nchans is a multiple of 4")
    delays = np.rint(
        np.float32(dm) * np.abs(delay_table(fch1, foff, nchans, tsamp))
    ).astype(np.int64)
    # on-pulse flag per emission sample; channel c at time t sees the
    # sample emitted at t - delay[c] (clamped to the start)
    lag = int(delays.max())
    src = np.maximum(np.arange(-lag, nsamps, dtype=np.int64), 0)
    on_at = ((src * tsamp / period) % 1.0) < duty
    rng = np.random.default_rng(seed)
    hdr = SigprocHeader(
        source_name="synth_psr", data_type=1, nchans=nchans, nbits=2,
        nifs=1, tsamp=tsamp, tstart=55000.0, fch1=fch1, foff=foff,
    )
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        write_sigproc_header(f, hdr)
        for t0 in range(0, nsamps, block):
            t = np.arange(t0, min(t0 + block, nsamps), dtype=np.int64)
            on = on_at[t[:, None] + (lag - delays)[None, :]]
            if amp < 1.0:
                on &= rng.random(on.shape, dtype=np.float32) < amp
            data = rng.integers(0, 3, size=on.shape, dtype=np.uint8)
            data += on
            f.write(pack_bits(data.ravel(), 2).tobytes())
    os.replace(tmp, path)
    return path


def pulsar_fil(directory: str, **params) -> str:
    """The filterbank ``write_pulsar_fil(**params)`` makes, written once
    under ``directory``. Its name hashes the parameters and this
    generator's source, so a changed parameter or generator never picks
    up an old file. Returns the path."""
    with open(__file__, "rb") as f:
        key = hashlib.sha256(f.read())
    key.update(repr(sorted(params.items())).encode())
    path = os.path.join(directory, f"synth-{key.hexdigest()[:16]}.fil")
    if not os.path.exists(path):
        os.makedirs(directory, exist_ok=True)
        write_pulsar_fil(path, **params)
    return path
