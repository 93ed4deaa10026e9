"""Registry of the Pallas TPU kernels: the twin/probe/fallback contract.

Every kernel in :mod:`peasoup_tpu.ops.pallas` ships as a TRIPLE — the
kernel itself, a bitwise (or envelope-gated) **jnp twin** used as the
oracle and as the path off TPU, and a **compile-and-run probe** in
``ops/pallas/__init__.py`` that checks the kernel against the twin at
the production shape on a TPU and raises if it fails.
The convention was enforced by review only; this registry makes it a
machine-checked contract: the audit's kernel engine
(:mod:`peasoup_tpu.analysis.kernels`) cross-references every entry
(PSK202), lowers every kernel under interpret mode at the registered
tiny geometry (PSK203), attempts Mosaic lowering where the toolchain
allows (PSK208), and flags any ``pl.pallas_call`` module that skips
registration (PSK201).

``build`` thunks close over all static/python arguments and expose only
array operands, so the audit can ``jax.jit(...).lower(...)`` them
without concretising statics; they are lazy — nothing imports jax until
a consumer runs them.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable


@dataclass(frozen=True)
class KernelSpec:
    """One registered Pallas kernel.

    ``probe`` names the ``probe_pallas_*`` gate in
    ``ops/pallas/__init__.py``; ``twin`` is the dotted path of the jnp
    oracle the probe must compare against; ``fallback`` names the path
    that runs off TPU.
    ``scalar_prefetch`` is the kernel's ``num_scalar_prefetch`` count
    (0 = no scalar-prefetch grid), cross-checked against the module AST
    (PSK206). ``lane_retile`` marks kernels that retile the lane
    dimension in-kernel; Mosaic refuses some retiles, so each marked
    kernel is compiled for v5e by tests/test_tpu_compile.py (PSK207
    flags lane retiles in kernels without the mark).
    """

    name: str
    module: str  # dotted module holding the entry point
    entry: str  # public entry-point function
    probe: str  # probe_pallas_* gate in ops/pallas/__init__.py
    twin: str  # dotted path of the jnp oracle / fallback
    fallback: str  # human description of the fallback ladder
    # build(interpret) -> (fn, array_args, kwargs); interpret=False
    # builds the Mosaic-lowered variant for TPU toolchain checks
    build: Callable[..., tuple[Callable, tuple, dict[str, Any]]]
    scalar_prefetch: int = 0
    lane_retile: bool = False


def _build_dedisperse(interpret: bool = True):
    import numpy as np

    from .dedisperse import dedisperse_pallas

    t, c, d = 2048, 8, 4
    fil = np.zeros((t, c), dtype=np.uint8)
    # delay table and killmask are host-side plan inputs (the entry
    # does host math on them), so the thunk closes over them and only
    # the filterbank is a traced operand
    delays = np.tile(
        np.arange(d, dtype=np.int32)[:, None] * 16, (1, c)
    )
    kill = np.ones(c, dtype=np.int32)
    out = t - int(delays.max())
    return (
        lambda f: dedisperse_pallas(
            f, delays, kill, out, scale=0.9, interpret=interpret
        ),
        (fil,),
        {},
    )


def _build_resample(interpret: bool = True):
    import numpy as np

    from .resample import resample_block_pallas

    n, block = 4096, 512
    x = np.zeros((1, n), dtype=np.float32)
    afs = np.asarray([[1e-9, -1e-9]], dtype=np.float32)
    return (
        lambda xx, aa: resample_block_pallas(
            xx, aa, block=block, interpret=interpret
        ),
        (x, afs),
        {},
    )


def _build_boxcar(interpret: bool = True):
    from ..singlepulse import (
        default_widths,
        plan_pad,
        prefix_sum_padded,
        width_extent,
        width_scales,
    )
    from .boxcar import boxcar_best_pallas

    import jax.numpy as jnp

    t = 2048
    widths = default_widths(4)
    tpad, span = plan_pad(t)
    wext = width_extent(widths)
    scales = width_scales(widths)
    csum = prefix_sum_padded(jnp.zeros((1, t), jnp.float32), tpad, wext)
    return (
        lambda cs: boxcar_best_pallas(
            cs, widths, scales, t, tpad, span=span, interpret=interpret
        ),
        (csum,),
        {},
    )


def _build_spchain(interpret: bool = True):
    from ..singlepulse import (
        default_widths,
        prefix_sum_padded,
        width_extent,
        width_scales,
    )
    from .spchain import boxcar_dec_best_pallas

    import jax.numpy as jnp

    span, dec = 1024, 32
    tpad = 2 * span
    widths = default_widths(6)
    wext = width_extent(widths)
    scales = width_scales(widths)
    nvalid = tpad - span // 2
    csum = prefix_sum_padded(
        jnp.zeros((1, nvalid), jnp.float32), tpad, wext
    )
    return (
        lambda cs: boxcar_dec_best_pallas(
            cs, widths, scales, nvalid, tpad, dec, span=span,
            interpret=interpret,
        ),
        (csum,),
        {},
    )


def _build_specchain(interpret: bool = True):
    import numpy as np

    from .specchain import SPEC_BLOCK, interp_deredden_zap_pallas

    nbins, d = SPEC_BLOCK + 129, 3
    re = np.zeros((d, nbins), dtype=np.float32)
    im = np.zeros((d, nbins), dtype=np.float32)
    med = np.ones((d, nbins), dtype=np.float32)
    zap = np.zeros(nbins, dtype=bool)
    return (
        lambda r, i, m, z: interp_deredden_zap_pallas(
            r, i, m, z, interpret=interpret
        ),
        (re, im, med, zap),
        {},
    )


def _build_interbin(interpret: bool = True):
    import numpy as np

    from .interbin import untwist_interbin_normalise

    block = 128
    m = 2 * block  # packed-DFT half length; must be a block multiple
    npad = m + block
    r = 2
    zr = np.zeros((r, m), dtype=np.float32)
    zi = np.zeros((r, m), dtype=np.float32)
    mean = np.zeros(r, dtype=np.float32)
    std = np.ones(r, dtype=np.float32)
    return (
        lambda a, b, mu, sd: untwist_interbin_normalise(
            a, b, mu, sd, npad=npad, block=block, interpret=interpret
        ),
        (zr, zi, mean, std),
        {},
    )


def _build_dftspec(interpret: bool = True):
    import numpy as np

    from .dftspec import dft_untwist_interbin, dftspec_supported

    n = 1 << 15  # geometry floor: n1 must be a multiple of 128
    m = n // 2
    npad = m + 128
    if not dftspec_supported(n, npad):  # pragma: no cover - static geo
        raise ValueError(f"dftspec geometry unsupported: n={n}")
    r = 2
    xe = np.zeros((r, m), dtype=np.float32)
    xo = np.zeros((r, m), dtype=np.float32)
    mean = np.zeros(r, dtype=np.float32)
    std = np.ones(r, dtype=np.float32)
    return (
        lambda a, b, mu, sd: dft_untwist_interbin(
            a, b, mu, sd, npad=npad, interpret=interpret
        ),
        (xe, xo, mean, std),
        {},
    )


def _build_harmpeaks(interpret: bool = True):
    import numpy as np

    import jax.numpy as jnp

    from .harmpeaks import find_harmonic_cluster_peaks
    from .peaks import PEAKS_BLOCK

    nharms = 2
    nlev = nharms + 1
    nbins = PEAKS_BLOCK
    sp = jnp.zeros((2, nbins), jnp.float32)
    windows = np.tile(
        np.asarray([[8, nbins - 8]], np.int32), (nlev, 1)
    )
    return (
        lambda s, w: find_harmonic_cluster_peaks(
            s, w, nharms=nharms, threshold=9.0, max_peaks=16,
            scales=(1.0, 0.5, 0.25), nbins=nbins, interpret=interpret,
        ),
        (sp, jnp.asarray(windows)),
        {},
    )


_KERNELS: tuple[KernelSpec, ...] = (
    KernelSpec(
        name="pallas.dedisperse",
        module="peasoup_tpu.ops.pallas.dedisperse",
        entry="dedisperse_pallas",
        probe="probe_pallas_dedisperse",
        twin="peasoup_tpu.ops.dedisperse.dedisperse_block",
        fallback="jnp gather scan (ops.dedisperse.dedisperse_block)",
        build=_build_dedisperse,
        scalar_prefetch=0,
    ),
    KernelSpec(
        name="pallas.resample",
        module="peasoup_tpu.ops.pallas.resample",
        entry="resample_block_pallas",
        probe="probe_pallas_resample",
        twin="peasoup_tpu.ops.resample.resample_accel",
        fallback="vmapped jnp resample (ops.resample.resample_accel)",
        build=_build_resample,
        scalar_prefetch=0,
    ),
    KernelSpec(
        name="pallas.boxcar",
        module="peasoup_tpu.ops.pallas.boxcar",
        entry="boxcar_best_pallas",
        probe="probe_pallas_boxcar",
        twin="peasoup_tpu.ops.singlepulse.boxcar_best_twin",
        fallback="jnp twin sweep (ops.singlepulse.boxcar_best_twin)",
        build=_build_boxcar,
        scalar_prefetch=3,
    ),
    KernelSpec(
        name="pallas.spchain",
        module="peasoup_tpu.ops.pallas.spchain",
        entry="boxcar_dec_best_pallas",
        probe="probe_pallas_spchain",
        twin="peasoup_tpu.ops.singlepulse.boxcar_dec_best_twin",
        fallback=(
            "jnp twin off TPU; the boxcar kernel + jnp dec-fold where "
            "the fold does not fit the span (select_sp_kernels)"
        ),
        build=_build_spchain,
        scalar_prefetch=3,
        lane_retile=True,
    ),
    KernelSpec(
        name="pallas.specchain",
        module="peasoup_tpu.ops.pallas.specchain",
        entry="interp_deredden_zap_pallas",
        probe="probe_pallas_specchain",
        twin="peasoup_tpu.ops.spectrum.interp_deredden_zap",
        fallback="unfused deredden->zap->interbin stanza (jnp twin)",
        build=_build_specchain,
        scalar_prefetch=1,  # the true-bins count rides SMEM prefetch
    ),
    KernelSpec(
        name="pallas.interbin",
        module="peasoup_tpu.ops.pallas.interbin",
        entry="untwist_interbin_normalise",
        probe="probe_pallas_interbin",
        twin="peasoup_tpu.ops.spectrum.form_interpolated_parts",
        fallback=(
            "packed-matmul rfft parts -> form_interpolated_parts -> "
            "normalise (the unfused jnp chain)"
        ),
        build=_build_interbin,
        scalar_prefetch=0,
    ),
    KernelSpec(
        name="pallas.dftspec",
        module="peasoup_tpu.ops.pallas.dftspec",
        entry="dft_untwist_interbin",
        probe="probe_pallas_dftspec",
        twin="peasoup_tpu.ops.pallas.dftspec.dft_untwist_interbin_twin",
        fallback="einsum four-step DFT + interbin kernel chain",
        build=_build_dftspec,
        scalar_prefetch=0,
        lane_retile=True,
    ),
    KernelSpec(
        name="pallas.harmpeaks",
        module="peasoup_tpu.ops.pallas.harmpeaks",
        entry="find_harmonic_cluster_peaks",
        probe="probe_pallas_harmpeaks",
        twin="peasoup_tpu.ops.harmonics.harmonic_sums",
        fallback=(
            "harmonic_sums(method='take') + jnp peaks pair per level"
        ),
        build=_build_harmpeaks,
        scalar_prefetch=0,
        # the MXU one-hot gather retiles its (SUB*K, BLOCK) dot output
        # back to the (SUB, BLOCK) tile
        lane_retile=True,
    ),
)


def kernel_specs() -> tuple[KernelSpec, ...]:
    """All registered kernels (import-cheap: thunks are lazy)."""
    return _KERNELS


def spec_for_module(stem: str) -> KernelSpec | None:
    """The registered spec whose module basename is ``stem``."""
    for spec in _KERNELS:
        if spec.module.rsplit(".", 1)[-1] == stem:
            return spec
    return None
