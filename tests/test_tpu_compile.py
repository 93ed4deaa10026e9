"""Compile the search's Pallas kernels for a described TPU v5e.

Interpret mode runs anywhere and cannot show what the chip's compiler
refuses (unaligned block shapes, lane retiles, VMEM overruns). The TPU
compiler is installed here and compiles for a chip that is described
and not attached (on-chip-measurement guide, section 2), so these tests
compile every kernel of the main path at the production shapes of
``chip_smoke.py``'s two phases — A, the tutorial shape (64 channels,
2^17-point spectra) and B, the survey beam (1024 channels, 2^21-point
spectra) — plus the single-pulse spchain kernel at its default span.
Nothing runs: a pass says the chip's compiler accepts the program, not
that it is right or fast.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite's workers all
import this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# the production geometry of chip_smoke.py's phases: FFT size, DM trials
# per search chunk (the auto-sized dm_block the chip chose in PR 21's
# smoke runs; 31 per chip at phase B) and the
# accel columns each DM trial pads to (identity-deduped lists pad to 4)
PHASES = {
    "A": dict(size=1 << 17, dm_block=59, accels=4),
    "B": dict(size=1 << 21, dm_block=31, accels=4),
}
NHARMS = 4  # peasoup's default -n
MAX_PEAKS = 128  # SearchConfig.max_peaks


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off:
    entries compiled for a described chip cannot be read back without
    one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def compile_on(chip, fn, *shapes):
    """Compile ``fn`` for ``chip`` at ``shapes`` ((shape, dtype) pairs)
    and check the kernel is in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def rows(phase: str) -> int:
    p = PHASES[phase]
    return p["dm_block"] * p["accels"]


def npad(phase: str) -> int:
    from peasoup_tpu.ops.pallas.peaks import PEAKS_BLOCK

    nbins = PHASES[phase]["size"] // 2 + 1
    return -(-nbins // PEAKS_BLOCK) * PEAKS_BLOCK


def level_windows(phase: str) -> np.ndarray:
    from peasoup_tpu.pipeline.search import _level_windows

    tsamp = 320e-6 if phase == "A" else 256e-6
    return _level_windows(PHASES[phase]["size"], NHARMS, 0.1, 1100.0, tsamp)


@pytest.mark.parametrize("phase,ndm,nchans", [
    ("A", 59, 64),  # the whole tutorial-shape trial set in one call
    ("B", 192, 357),  # one DM segment x one channel chunk of the beam
])
def test_dedisperse(one_chip, phase, ndm, nchans):
    import chip_smoke
    from peasoup_tpu.ops.pallas.dedisperse import dedisperse_pallas
    from peasoup_tpu.plan.dm_plan import DMPlan

    ph = chip_smoke.TUTORIAL if phase == "A" else chip_smoke.SURVEY
    plan = DMPlan.create(
        nsamps=ph.nsamps, nchans=ph.nchans, tsamp=ph.tsamp, fch1=ph.fch1,
        foff=ph.foff, dm_start=0.0, dm_end=float(ph.flags[1]),
        pulse_width=64.0, tol=1.10,
    )
    delays = plan.delay_samples()[:ndm, :nchans]
    kill = np.ones(nchans, np.int32)
    compile_on(
        one_chip,
        lambda f: dedisperse_pallas(f, delays, kill, plan.out_nsamps),
        ((ph.nsamps, nchans), jnp.uint8),
    )


@pytest.mark.parametrize("phase", ["A", "B"])
def test_resample(one_chip, phase):
    from peasoup_tpu.ops.pallas.resample import (
        choose_block, resample_block_pallas,
    )
    from peasoup_tpu.ops.resample import accel_factor

    n = PHASES[phase]["size"]
    # the widest af of a +-5 m/s^2 plane, as the README's Quick start
    af = float(np.abs(accel_factor(np.asarray([5.0]), 256e-6)).max())
    block = choose_block(af, n)
    assert block
    d, a = PHASES[phase]["dm_block"], 16
    compile_on(
        one_chip,
        lambda x, afs: resample_block_pallas(x, afs, block=block),
        ((d, n), jnp.float32), ((d, a), jnp.float32),
    )


@pytest.mark.parametrize("phase", ["A", "B"])
def test_interbin(one_chip, phase):
    from peasoup_tpu.ops.pallas.interbin import untwist_interbin_normalise
    from peasoup_tpu.ops.pallas.peaks import PEAKS_BLOCK

    r, m = rows(phase), PHASES[phase]["size"] // 2
    compile_on(
        one_chip,
        lambda zr, zi, mu, sd: untwist_interbin_normalise(
            zr, zi, mu, sd, npad=npad(phase), block=PEAKS_BLOCK
        ),
        ((r, m), jnp.float32), ((r, m), jnp.float32),
        ((r,), jnp.float32), ((r,), jnp.float32),
    )


def test_dftspec(one_chip):
    """Phase A's spectra; at phase B's 2^21 points the kernel is gated
    off (dftspec_supported) and the interbin kernel takes over."""
    from peasoup_tpu.ops.pallas.dftspec import (
        dft_untwist_interbin, dftspec_supported, plane_factors,
    )

    size, r = PHASES["A"]["size"], rows("A")
    assert dftspec_supported(size, npad("A"))
    assert not dftspec_supported(PHASES["B"]["size"], npad("B"))
    n1, n2 = plane_factors(size // 2)
    compile_on(
        one_chip,
        lambda xe, xo, mu, sd: dft_untwist_interbin(
            xe, xo, mu, sd, npad=npad("A")
        ),
        ((r, n1, n2), jnp.float32), ((r, n1, n2), jnp.float32),
        ((r,), jnp.float32), ((r,), jnp.float32),
    )


@pytest.mark.parametrize("phase", ["A", "B"])
def test_specchain(one_chip, phase):
    from peasoup_tpu.ops.pallas.specchain import interp_deredden_zap_pallas

    d = PHASES[phase]["dm_block"]
    nbins = PHASES[phase]["size"] // 2 + 1
    compile_on(
        one_chip,
        interp_deredden_zap_pallas,
        ((d, nbins), jnp.float32), ((d, nbins), jnp.float32),
        ((d, nbins), jnp.float32), ((nbins,), jnp.bool_),
    )


@pytest.mark.parametrize("phase", ["A", "B"])
def test_peaks(one_chip, phase):
    from peasoup_tpu.ops.pallas import peaks

    assert peaks._SUB == 24
    nlev = NHARMS + 1
    scales = tuple(2.0 ** (-h / 2.0) for h in range(nlev))
    windows = jnp.asarray(level_windows(phase))
    nbins = PHASES[phase]["size"] // 2 + 1
    compile_on(
        one_chip,
        lambda s: peaks.find_cluster_peaks_multi(
            [s] * nlev, windows, threshold=9.0, max_peaks=MAX_PEAKS,
            scales=scales, nbins=nbins,
        ),
        ((PHASES[phase]["dm_block"], PHASES[phase]["accels"], npad(phase)),
         jnp.float32),
    )


@pytest.mark.parametrize("phase", ["A", "B"])
def test_harmpeaks(one_chip, phase):
    from peasoup_tpu.ops.pallas.harmpeaks import find_harmonic_cluster_peaks

    scales = tuple(2.0 ** (-h / 2.0) for h in range(NHARMS + 1))
    windows = jnp.asarray(level_windows(phase))
    nbins = PHASES[phase]["size"] // 2 + 1
    compile_on(
        one_chip,
        lambda s: find_harmonic_cluster_peaks(
            s, windows, nharms=NHARMS, threshold=9.0,
            max_peaks=MAX_PEAKS, scales=scales, nbins=nbins,
        ),
        ((PHASES[phase]["dm_block"], PHASES[phase]["accels"], npad(phase)),
         jnp.float32),
    )


def test_spchain(one_chip):
    """The single-pulse search's fused sweep + dec-fold at its default
    span (8192), widths (12) and decimation (32), over phase B's series."""
    from peasoup_tpu.ops.pallas.spchain import boxcar_dec_best_pallas
    from peasoup_tpu.ops.singlepulse import (
        default_widths, plan_pad, width_extent, width_scales,
    )
    from peasoup_tpu.pipeline.single_pulse import SinglePulseConfig

    cfg = SinglePulseConfig()
    nsamps = PHASES["B"]["size"]
    widths = default_widths(cfg.n_widths)
    tpad, span = plan_pad(nsamps)
    assert span == 8192
    compile_on(
        one_chip,
        lambda cs: boxcar_dec_best_pallas(
            cs, widths, width_scales(widths), nsamps, tpad, cfg.decimate,
            span=span,
        ),
        ((16, tpad + width_extent(widths)), jnp.float32),
    )


def test_sharded_search_on_four_chips(topo):
    """chip_smoke --multichip's program: phase B's search with the DM
    axis over the 2x2 mesh, each chip running the one-chip kernels."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from peasoup_tpu.parallel.sharded_search import make_sharded_search_fn

    mesh = Mesh(np.array(topo.devices[:4]), ("dm",))
    fn = make_sharded_search_fn(
        mesh, 9.0, pallas_block=0, select_smax=1, pallas_peaks=True,
        fused_interbin=True, mega_harm=True, fused_dft=False,
        fused_spec=True,
    )
    size = PHASES["B"]["size"]
    d = 4 * PHASES["B"]["dm_block"]
    dm, rep = NamedSharding(mesh, P("dm")), NamedSharding(mesh, P())
    args = (
        jax.ShapeDtypeStruct((d, size), jnp.uint8, sharding=dm),
        jax.ShapeDtypeStruct((d, 4), jnp.float32, sharding=dm),
        jax.ShapeDtypeStruct((size // 2 + 1,), jnp.bool_, sharding=rep),
        jax.ShapeDtypeStruct((NHARMS + 1, 2), jnp.int32, sharding=rep),
    )
    text = fn.lower(
        *args, size=size, nsamps_valid=size, nharms=NHARMS,
        max_peaks=MAX_PEAKS, pos5=26, pos25=268,
    ).compile().as_text()
    # specchain, interbin and harmpeaks on every chip; no collectives
    assert text.count("tpu_custom_call") == 3
    assert "all-gather" not in text and "all-reduce" not in text
