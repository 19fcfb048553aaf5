"""Compile-only guards: every Pallas kernel of the coadd path lowers through
Mosaic for a TPU v5e, at the engine's shapes.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, vector shape casts, VMEM and
SMEM overruns.  These tests compile each kernel for a described (not
attached) v5e and check that the program holds a Mosaic ``tpu_custom_call``.
Nothing runs, so nothing here needs a chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.warp import warp

N_IMAGES, FRAME = 16, 32        # one pack of 32x32 benchmark frames
PSF_TAPS = 13                   # SurveyConfig.psf_stamp_size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _f32(*shape):
    return (shape, jnp.float32)


def _stack(npix, extra=()):
    """Shapes of (pixels, wcs, accepts, grid_ra, grid_dec) + extras."""
    return (_f32(N_IMAGES, FRAME, FRAME), _f32(N_IMAGES, 8), _f32(N_IMAGES),
            _f32(npix, npix), _f32(npix, npix)) + tuple(extra)


def _rows(npix, psf=()):
    return warp.autotune_block_rows(
        npix, FRAME, FRAME,
        psf_kernel_width=psf[-1] if psf else 0, psf_kernel_2d=len(psf) == 2,
    )


@pytest.mark.parametrize("npix", [32, 48, 64, 128])
def test_coadd_fused_compiles(one_chip, npix):
    br = _rows(npix)
    hlo = _compiled_text(
        one_chip,
        lambda p, w, a, gr, gd: warp.coadd_fused(
            p, w, a, gr, gd, block_rows=br, interpret=False),
        *_stack(npix),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("npix", [64, 128])
@pytest.mark.parametrize("taps", [(PSF_TAPS,), (PSF_TAPS, PSF_TAPS)],
                         ids=["separable", "psf2d"])
def test_coadd_fused_psf_compiles(one_chip, npix, taps):
    br = _rows(npix, taps)
    hlo = _compiled_text(
        one_chip,
        lambda p, w, a, gr, gd, k: warp.coadd_fused(
            p, w, a, gr, gd, psf_kernels=k, block_rows=br, interpret=False),
        *_stack(npix, [_f32(N_IMAGES, *taps)]),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("npix", [64, 128])
@pytest.mark.parametrize("kind", ["moments", "hist", "clip"])
def test_robust_kernels_compile(one_chip, npix, kind):
    br = _rows(npix)
    if kind == "moments":
        fn = lambda p, w, a, gr, gd: warp.coadd_moments(  # noqa: E731
            p, w, a, gr, gd, block_rows=br, interpret=False)
        extra = []
    elif kind == "hist":
        fn = lambda p, w, a, gr, gd, lo, iw: warp.coadd_hist(  # noqa: E731
            p, w, a, gr, gd, lo, iw, nbins=16, block_rows=br, interpret=False)
        extra = [_f32(npix, npix)] * 2
    else:
        fn = lambda p, w, a, gr, gd, c, t: warp.coadd_clip(  # noqa: E731
            p, w, a, gr, gd, c, t, block_rows=br, interpret=False)
        extra = [_f32(npix, npix)] * 2
    assert "tpu_custom_call" in _compiled_text(one_chip, fn,
                                               *_stack(npix, extra))


@pytest.mark.parametrize("bricks,brick_npix,npix",
                         [(4, 64, 128), (9, 64, 192)])
def test_mosaic_bricks_compiles(one_chip, bricks, brick_npix, npix):
    hlo = _compiled_text(
        one_chip,
        lambda t, c, o: warp.mosaic_bricks(t, c, o, npix, interpret=False),
        _f32(bricks, brick_npix, brick_npix),
        _f32(bricks, brick_npix, brick_npix),
        ((bricks, 2), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("npix", [64, 128])
def test_coadd_fused_batched_queries_compile(one_chip, npix):
    """A coalesced batch vmaps the kernel over queries (`execute_batch`,
    `run_distributed`): its per-image SMEM tables must still block legally."""
    br = _rows(npix)

    def one(p, w, a, gr, gd):
        return warp.coadd_fused(p, w, a, gr, gd, block_rows=br,
                                interpret=False)

    hlo = _compiled_text(
        one_chip,
        jax.vmap(one, in_axes=(None, None, 0, 0, 0)),
        _f32(N_IMAGES, FRAME, FRAME), _f32(N_IMAGES, 8), _f32(3, N_IMAGES),
        _f32(3, npix, npix), _f32(3, npix, npix),
    )
    assert "tpu_custom_call" in hlo


def test_resident_scan_compiles_at_sdss_size(one_chip):
    """The XLA-lane query program at deployment size — 480 SDSS frames
    (2048x1489) resident in 30 packs of 16, one 1024^2 output — compiles
    for one v5e and fits its 16 GB next to the archive."""
    from repro.core.engine import _coadd_scan_sparse
    from repro.core.seqfile import FLOAT_COLS, META_COLS

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    packs, cap, q = 30, 16, 1024
    lowered = _coadd_scan_sparse.lower(
        shape((packs, cap, 2048, 1489)), shape((packs, cap, 8)),
        {k: shape((packs, cap), jnp.int32) for k in META_COLS},
        {k: shape((packs, cap)) for k in FLOAT_COLS},
        None, shape((2,), jnp.int32), shape((2, cap), jnp.bool_),
        shape((7,)), shape((q, q)), shape((q, q)),
        use_kernel=False, block_rows=None,
    )
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


@pytest.mark.parametrize("hw,lanes", [((2048, 1489), "y"), ((1489, 2048), "x")],
                         ids=["rows-on-lanes", "published"])
def test_windowed_scan_compiles_at_sdss_size(one_chip, monkeypatch, hw, lanes):
    """The benchmark cell's query program on the windowed path: 60 resident
    packs of 16 SDSS frames, four gated, one 1024^2 output, with frames
    stored 2048 rows by 1489 columns (as the benchmark's archive holds
    them) or in the published orientation, 1489 rows by 2048 columns.  XLA
    lays each out with a 2048-pixel axis on the lanes, which `lane_axis`
    reads back from the compiled program; the kernel reads that layout
    through a bitcast, never a copy of it, and the program's working set,
    the guard's gather fallback included, stays a few MB next to the
    11.7 GB archive.

    The CPU backend would interpret the kernel: the test turns that off
    (and drops the traces cached either way) to lower it through Mosaic."""
    from repro.core.engine import _coadd_scan_sparse
    from repro.core.seqfile import FLOAT_COLS, META_COLS
    from repro.kernels.warp import ops, windowed

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    packs, cap, q = 60, 16, 1024
    fit = windowed.WindowFit(*windowed.TILES[0], 24, windowed.WIN_L, lanes)
    compiled = _coadd_scan_sparse.lower(
        shape((packs, cap) + hw), shape((packs, cap, 8)),
        {k: shape((packs, cap), jnp.int32) for k in META_COLS},
        {k: shape((packs, cap)) for k in FLOAT_COLS},
        None, shape((4,), jnp.int32), shape((4, cap), jnp.bool_),
        shape((7,)), shape((q, q)), shape((q, q)),
        use_kernel=False, block_rows=None, window=fit,
    ).compile()
    jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
    m2m = compiled.input_formats[0][0].layout.major_to_minor
    assert windowed._VIEWS[lanes] == tuple(m2m)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6


def test_query_grid_compiles_in_the_scans_layout(one_chip, monkeypatch):
    """The query's output grid program (`mapper.query_grid`) at the
    benchmark's 1024^2: it compiles for one v5e with a working set of a few
    hundred KB, and its two outputs leave in the layout in which the
    windowed scan program takes its grid operands, so the runtime passes
    them to the scan as they are, with no relayout copy between the two."""
    from repro.core import mapper
    from repro.core.engine import _coadd_scan_sparse
    from repro.core.seqfile import FLOAT_COLS, META_COLS
    from repro.kernels.warp import ops, windowed

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    packs, cap, q = 60, 16, 1024
    grid = mapper.query_grid.lower(shape((12,)), q).compile()
    fit = windowed.WindowFit(*windowed.TILES[0], 24, windowed.WIN_L, "y")
    scan = _coadd_scan_sparse.lower(
        shape((packs, cap, 2048, 1489)), shape((packs, cap, 8)),
        {k: shape((packs, cap), jnp.int32) for k in META_COLS},
        {k: shape((packs, cap)) for k in FLOAT_COLS},
        None, shape((4,), jnp.int32), shape((4, cap), jnp.bool_),
        shape((7,)), shape((q, q)), shape((q, q)),
        use_kernel=False, block_rows=None, window=fit,
    ).compile()
    jax.clear_caches()
    outs = grid.output_formats
    assert [o.layout for o in outs] == [f.layout for f in scan.input_formats[0][-2:]]
    assert all(o.layout.major_to_minor == (0, 1) for o in outs)
    assert grid.memory_analysis().temp_size_in_bytes < 1e6
