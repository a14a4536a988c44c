"""The main path's kernels, compiled for a DESCRIBED TPU v5e at the
benchmark cells' real width and rows (ISSUE 27).

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached: what Mosaic would refuse on the chip (a
slice off the tiling, too much VMEM, a loop it cannot lower) it refuses
here, at no chip time.  Nothing runs, so this says nothing about results
or speed.  The topology is described inside a module-scoped fixture,
never at import (one process at a time may load the TPU's library, and
every xdist worker imports every test file), and these tests live in
this ONE file so a single worker loads it."""

import functools
import os

import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram_pallas import (
    bin_rows_view, build_histogram_pallas_leaves,
    build_histogram_pallas_leaves_q8, traced_kernels,
    wave_row_update_pallas)

# the cells of BENCHMARK.json: 21.25M rows (padded to the row block) x 67
F, N, MAX_BIN = 67, 21_250_048, 255


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in (("TPU_LOG_DIR", "disabled"),
                 ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                 ("TPU_WORKER_HOSTNAMES", "localhost"),
                 ("TPU_SKIP_MDS_QUERY", "1")):
        os.environ.setdefault(k, v)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent
    # cache but cannot be read back without the chip (the next run would
    # warn and compile again): keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def S(one_chip):
    """``S(shape, dtype)``: an argument's shape on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("kind", ["q8", "bf16"])
def test_leaf_dma_kernel_compiles_at_cell_shape(S, kind):
    if kind == "q8":
        build, wdt, cdt = build_histogram_pallas_leaves_q8, jnp.int8, jnp.int8
        name = f"lgbm_hist_leaves_q8_dma_f96_fc72_b256_g8_kr4096_n{N}"
    else:
        build, wdt, cdt = (build_histogram_pallas_leaves, jnp.bfloat16,
                           jnp.int32)
        name = f"lgbm_hist_leaves_dma_f96_fc72_b256_g4_kr4096_n{N}"

    compiled = jax.jit(
        lambda bins, w, ch: build(bins, w, ch, num_bins=MAX_BIN,
                                  pipeline="dma", interpret=False)
    ).lower(S((F, N), jnp.uint8), S((8, N), wdt), S((N,), cdt)).compile()
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic took the kernel
    assert name in traced_kernels()


@pytest.mark.parametrize("kind", ["q8", "bf16"])
def test_compacted_leaf_pass_compiles_at_cell_shape(S, kind):
    """A wave's or the endgame's pass as the grower calls it (ISSUE 31):
    the plan kernel (a ragged last step), the compaction kernel (selection
    matmuls, stores at a runtime lane offset, N / 512 window offsets in
    scalar memory) and behind it the
    leaf kernel with its trip count read from a prefetched scalar."""
    if kind == "q8":
        build, wdt, g = build_histogram_pallas_leaves_q8, jnp.int8, 8
    else:
        build, wdt, g = build_histogram_pallas_leaves, jnp.bfloat16, 4
    compiled = jax.jit(
        lambda bins, w, ch: build(bins, w, ch, num_bins=MAX_BIN,
                                  pipeline="dma", interpret=False,
                                  compact=True)
    ).lower(S((F, N), jnp.uint8), S((8, N), wdt), S((N,), jnp.int8)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    names = traced_kernels()
    assert f"lgbm_hist_compact_plan_s512_r1024_n{N}" in names
    assert f"lgbm_hist_compact_dma_f96_fc72_s512_kb8192_n{N}" in names
    q = "_q8" if kind == "q8" else ""
    assert (f"lgbm_hist_leaves{q}_dma_f96_fc72_b256_g{g}_kr4096_n{N + 8192}"
            in names)


@pytest.mark.parametrize("w", [42, 25])       # q8's and exact's wave widths
def test_row_update_fetch_kernel_compiles_at_cell_shape(S, w):
    """The row update as the grower calls it: the (F, 8, N/8) view of the
    bin matrix and W feature ids in, W column copies a row block."""
    def route(bins, feats, rl, tab):
        return wave_row_update_pallas(
            bin_rows_view(bins, "dma"), rl, tab, feats=feats,
            pipeline="dma", interpret=False)

    compiled = jax.jit(route).lower(
        S((F, N), jnp.uint8), S((w,), jnp.int32), S((N,), jnp.uint8),
        S((8, w), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"lgbm_wave_row_update_dma_w{w}_f{F}_kr16384_n{N}" in \
        traced_kernels()
    # nothing of W columns is built in front of the kernel
    assert "concatenate" not in text
    assert f"u8[{F},8,{N // 8}]" in text


def test_cat_row_update_kernel_compiles_at_cell_shape(S):
    """``criteo-cat-q8.train`` (PR 34): 45,840,617 rows padded to the row
    block x 39 columns; the split table carries ``is_cat`` and eight words
    of left-set bins a slot below its eight scalar rows."""
    f, n, w, b = 39, 45_842_432, 42, 255

    def route(bins, feats, rl, tab, is_cat, member):
        return wave_row_update_pallas(
            bin_rows_view(bins, "dma"), rl, tab, feats=feats,
            cat=(is_cat, member), pipeline="dma", interpret=False)

    compiled = jax.jit(route).lower(
        S((f, n), jnp.uint8), S((w,), jnp.int32), S((n,), jnp.uint8),
        S((8, w), jnp.int32), S((w,), jnp.bool_),
        S((w, b), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"lgbm_wave_row_update_dma_cat_w{w}_f{f}_kr16384_n{n}" in \
        traced_kernels()
    assert f"u8[{f},8,{n // 8}]" in text and f"s32[17,{w}]" in text


def test_efb_row_update_kernel_compiles_at_cell_shape(S):
    """``allstate-efb-q8.train`` (PR 38): 12,184,290 rows padded to the row
    block x 48 bundle columns; a slot whose split feature lives in a bundle
    carries the set of bundle codes that go left where a categorical slot
    carries its categories, and the kernel's name says ``_efb``."""
    g, n, w = 48, 12_185_600, 42

    def route(bins, feats, rl, tab, bundled, go):
        return wave_row_update_pallas(
            bin_rows_view(bins, "dma"), rl, tab, feats=feats,
            cat=(bundled, go), bundled=True, pipeline="dma", interpret=False)

    compiled = jax.jit(route).lower(
        S((g, n), jnp.uint8), S((w,), jnp.int32), S((n,), jnp.uint8),
        S((8, w), jnp.int32), S((w,), jnp.bool_),
        S((w, 256), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"lgbm_wave_row_update_dma_efb_w{w}_f{g}_kr4096_n{n}" in traced_kernels()
    assert f"u8[{g},8,{n // 8}]" in text and f"s32[17,{w}]" in text


def test_goss_sampler_compiles_at_cell_rows(S):
    """The GOSS draw of ``criteo-q8-goss.train`` (PR 32): one program over
    the cell's 21,250,000 rows whose exact threshold is a loop of counting
    passes, with no sort and no temporaries of its own."""
    from lightgbm_tpu.models.gbdt import goss_sample
    rows = 21_250_000
    compiled = goss_sample.lower(
        S((rows,), jnp.float32), S((rows,), jnp.float32), S((), jnp.int32),
        top_rate=0.2, other_rate=0.1, bagging_seed=3).compile()
    text = compiled.as_text()
    assert "while" in text and " sort(" not in text
    mem = compiled.memory_analysis()
    # classes (1 B a row), mask and the two scaled vectors (4 B a row each)
    assert mem.output_size_in_bytes < 13.5 * rows
    assert mem.temp_size_in_bytes < 4 * rows


# score rows, row_leaf rows (padded to the row block), small-shape probes
@pytest.mark.parametrize("rows,leaf_rows,leaves", [
    (21_250_000, N, 255), (45_840_617, 45_842_432, 255),
    (21_250_000, N, 8192), (64, 4096, 255), (600, 600, 7)])
def test_score_update_kernel_compiles_in_place(S, rows, leaf_rows, leaves):
    """The select lowering, donated as its entry is (PR 35), at the cells'
    own lengths, which no block divides, at the largest table it takes and
    at the 64-row probes the drivers build: Mosaic takes the 1-D blocks,
    the score is aliased and nothing is copied, padded or sliced around
    the kernel."""
    from lightgbm_tpu.models.gbdt import (SCORE_DONATE_ARGNUMS,
                                          _score_select_impl)
    compiled = jax.jit(
        functools.partial(_score_select_impl, interpret=False),
        donate_argnums=SCORE_DONATE_ARGNUMS).lower(
        S((rows,), jnp.float32), S((leaf_rows,), jnp.int32),
        S((leaves,), jnp.float32), S((), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert any(k.startswith(f"lgbm_score_update_l{leaves}_")
               and k.endswith(f"_n{rows}") for k in traced_kernels())
    assert "output_to_operand_aliasing={{}: (2, {})}" in text
    assert " pad(" not in text and "gather" not in text
    mem = compiled.memory_analysis()
    # the score is an alias (sizes are whole tiles), temporaries: none
    assert mem.temp_size_in_bytes < 4096
    assert 4 * rows <= mem.alias_size_in_bytes < 4 * rows + 4096


def test_score_update_over_four_chips_has_no_collective(topo):
    """``criteo-q8-dp4.train``: 85M scores and leaf ids as row shards of
    the 2x2 mesh; each chip runs the kernel on its 21.25M rows in place."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.models.gbdt import _update_score_by_select_sharded
    mesh = Mesh(np.array(topo.devices), ("workers",))
    rows, by_rows = 85_000_000, NamedSharding(mesh, P("workers"))
    whole = NamedSharding(mesh, P())
    compiled = _update_score_by_select_sharded(
        mesh, "workers", interpret=False).lower(
        jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=by_rows),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=by_rows),
        jax.ShapeDtypeStruct((255,), jnp.float32, sharding=whole),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=whole)).compile()
    text = compiled.as_text()
    assert f"lgbm_score_update_l255_kr65536_n{rows // 4}" in traced_kernels()
    assert "tpu_custom_call" in text and f"f32[{rows // 4}]" in text
    for collective in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all"):
        assert collective not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4096
