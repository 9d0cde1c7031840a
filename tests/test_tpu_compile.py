"""Mosaic's verdict on the kernels of the main paths at real widths (the
ALS dense gram and lanes solve; the sequence engine's selected attention
and lightning update, its Mamba-2 update and the program around it), with no
chip: libtpu compiles for a described v5e in the sandbox (PERF.md, PR 21).
It proves compilation, not results.  The topology is described inside a
fixture, by the one worker that runs this file; keep every such test
here."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rank,rows,n_src,implicit", [
    (64, 96, 480_189, False),     # als-netflix-r64's item side
    (64, 4096, 17_770, True),     # its user side, implicit weights
    (128, 64, 480_189, False),    # the widest rank that plans dense rows
], ids=["r64-items", "r64-users-implicit", "r128"])
def test_dense_gram_kernel_compiles_for_v5e(one_chip, rank, rows, n_src,
                                            implicit):
    from predictionio_tpu.ops.pallas_kernels import (
        DENSE_BLOCK_DTYPE, dense_block_width, fused_gram_dense_pallas,
    )

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda block, x, alpha: fused_gram_dense_pallas(
            block, x, alpha, implicit=implicit)).lower(
        shape((rows, dense_block_width(n_src)), DENSE_BLOCK_DTYPE),
        shape((n_src, rank), jnp.bfloat16),
        shape((), jnp.float32)).compile()
    # the name the benchmark's als_gram_roofline and als_dense_gram_ms read
    assert "fused_gram_dense_pallas" in compiled.as_text()


# The lanes solve in the shapes its slices differ by: als-netflix-r64's
# largest user-side batch (its dense rows) at the cell's rank, the
# templates' default rank 10 (a last block of 2 rows and 2 columns) and
# the last rank lanes_solve_fits_vmem admits, which has to fit VMEM.

@pytest.mark.parametrize("batch,rank", [(23_488, 64), (6_040, 10),
                                        (6_040, 70)])
def test_lanes_solve_kernel_compiles_for_v5e(one_chip, batch, rank):
    from predictionio_tpu.ops.pallas_kernels import (
        lanes_solve_fits_vmem, ridge_solve_lu_pallas,
    )

    def shape(dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    assert lanes_solve_fits_vmem(rank)
    compiled = ridge_solve_lu_pallas.lower(
        shape((batch, rank, rank)), shape((batch, rank)),
        shape((batch,))).compile()
    # the name the benchmark's als_solve_roofline reads
    assert "ridge_solve_lu_pallas" in compiled.as_text()


# The block-selected / lightning backbone's kernels at the published
# widths (32 lightning heads and 2 groups of 16 query heads of 128), in
# the tile shapes of its programs: 8 events a tile for turns, 64 for
# prefill chunks.

@pytest.mark.parametrize("tiles,tq", [(96, 8), (24, 64), (80, 64)])
def test_lightning_kernel_compiles_for_v5e(one_chip, tiles, tq):
    from predictionio_tpu.ops import sala_kernels

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qkv = shape((tiles, 32, tq, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, state, rate, *per_tile:
        sala_kernels._lightning_pallas(q, k, v, state, rate, *per_tile,
                                       scale=128 ** -0.5, hb=8,
                                       interpret=False)).lower(
        qkv, qkv, qkv, shape((194, 32, 128, 128), jnp.float32),
        shape((32,), jnp.float32),
        *[shape((tiles,), jnp.int32)] * 4).compile()
    # the name the benchmark's lightning_ms and lightning_roofline read
    assert "sala_lightning" in compiled.as_text()


@pytest.mark.parametrize("tiles,tq,u_max,pb", [
    (96, 8, 336, 8), (24, 64, 640, 4), (80, 64, 640, 4)])
def test_sparse_attention_kernel_compiles_for_v5e(one_chip, tiles, tq,
                                                  u_max, pb):
    from predictionio_tpu.ops import sala_kernels

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, meta, cnt, pages, pool:
        sala_kernels._sparse_attention_pallas(
            q, meta, cnt, pages, pool, page=128, block=64, topk=64, pb=pb,
            interpret=False)).lower(
        shape((tiles, 2, 16 * tq, 128), jnp.bfloat16),
        shape((tiles, 2, tq, 128), jnp.int32), shape((tiles, 2), jnp.int32),
        shape((tiles, 2, u_max), jnp.int32),
        shape((24_000 * 128, 512), jnp.bfloat16)).compile()
    # the name sparse_attn_ms and sparse_attn_roofline read
    assert "sala_sparse_attention" in compiled.as_text()


# The Mamba / sliding-window / shared-cache backbone's kernels at the
# published widths (E 5,120, N 16; 10 kv-head pairs of 128 lanes, a page
# row of 2,560), in the tile shapes of its programs: 8 events a tile for
# turns, 64 for prefill chunks, a read row's 16 query rows over a table
# of 320 pages.

@pytest.mark.parametrize("tiles,tq", [(97, 8), (81, 64)])
def test_selective_scan_kernel_compiles_for_v5e(one_chip, tiles, tq):
    from predictionio_tpu.ops import sambay_kernels

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, cols = shape((tiles, tq, 5120)), shape((tiles, 16, tq))
    compiled = jax.jit(
        lambda x, delta, bt, ct, a, d, state, *per_tile:
        sambay_kernels._scan_pallas(
            x, delta, bt, ct, a, d, state, *per_tile,
            eb=sambay_kernels.SCAN_BLOCK, interpret=False)).lower(
        rows, rows, cols, cols, shape((16, 5120)), shape((1, 5120)),
        shape((130, 16, 5120)),
        *[shape((tiles,), jnp.int32)] * 4).compile()
    # the name the benchmark's ssm_scan_ms and ssm_scan_roofline read
    assert "sambay_selective_scan" in compiled.as_text()


@pytest.mark.parametrize("name,tiles,rows,u_max,window,pages", [
    ("sambay_window_attention", 97, 32, 8, 512, 394),
    ("sambay_window_attention", 81, 256, 8, 512, 394),
    ("sambay_shared_attention", 64, 16, 320, 0, 6401)])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, name, tiles, rows,
                                                 u_max, window, pages):
    from predictionio_tpu.ops import sambay_kernels

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, qpos, cnt, lists, pool:
        sambay_kernels._attention_pallas(
            q, qpos, cnt, lists, pool, page=128, window=window, pb=4,
            name=name, interpret=False)).lower(
        shape((tiles, 10, rows, 128), jnp.bfloat16),
        shape((tiles, rows), jnp.int32), shape((tiles,), jnp.int32),
        shape((tiles, u_max), jnp.int32),
        shape((pages * 128, 2560), jnp.bfloat16)).compile()
    # the names window_attn_ms / shared_attn_ms and their rooflines read
    assert name in compiled.as_text()


# The Mamba-2 / no-position attention backbone's kernel at the published
# widths (64 heads of 64 over a state of 128, 66 slots of 2 MiB a layer),
# in the tile shapes of its programs: 16 events a tile for turns (32
# users and the 128-token bucket's spare tiles), 64 for a 1,024-event
# prefill chunk; and its attention layers' call of the paged-attention
# kernel (4 kv pairs, 8 query rows an event, a table of 256 pages).

@pytest.mark.parametrize("tiles,tq", [(41, 16), (25, 64)])
def test_ssd_update_kernel_compiles_for_v5e(one_chip, tiles, tq):
    from predictionio_tpu.ops import granite_h_kernels

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, heads = shape((tiles, tq, 64 * 64)), shape((tiles, tq, 64))
    compiled = jax.jit(
        lambda x, dt, cs, dl, b, c, state, *per_tile:
        granite_h_kernels._ssd_pallas(
            x, dt, cs, dl, b, c, state, *per_tile,
            hb=granite_h_kernels.HEAD_BLOCK, interpret=False),
        donate_argnums=(6,)).lower(
        rows, heads, heads, shape((tiles, 64)),
        shape((tiles, tq, 128), jnp.bfloat16),
        shape((tiles, tq, 128), jnp.bfloat16), shape((66, 64, 64, 128)),
        *[shape((tiles,), jnp.int32)] * 4).compile()
    # the name the benchmark's ssd_update_ms and ssd_update_roofline read
    assert "granite_h_ssd_update" in compiled.as_text()


@pytest.mark.parametrize("tiles,tq", [(41, 16), (25, 64)])
def test_gqa_attention_kernel_compiles_for_v5e(one_chip, tiles, tq):
    from predictionio_tpu.ops import sambay_kernels

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows = 8 * tq
    compiled = jax.jit(
        lambda q, qpos, cnt, lists, pool:
        sambay_kernels._attention_pallas(
            q, qpos, cnt, lists, pool, page=128, window=0, pb=4,
            name="granite_h_gqa_attention", interpret=False)).lower(
        shape((tiles, 4, rows, 128), jnp.bfloat16),
        shape((tiles, rows), jnp.int32), shape((tiles,), jnp.int32),
        shape((tiles, 256), jnp.int32),
        shape((2901 * 128, 1024), jnp.bfloat16)).compile()
    # the name gqa_attn_ms and gqa_attn_roofline read
    assert "granite_h_gqa_attention" in compiled.as_text()


# The Mamba-2 mixer's arrays between its two products (PERF.md §6, PR 44):
# the (128, 32) program of a two-layer backbone (one ``mamba``, one
# ``attention``) at the published widths over the cell's 66 slots, the
# arguments row-major as ``device_put`` leaves them on the chip.  Every
# array of the Mamba-2 layer keeps its channels along the lanes from the
# in-projection to the out-projection: nothing float32 of a megabyte is
# shaped ``[..., 64, 64]`` (the head-major view), no state array of the
# pool's 66 slots is copied or reshaped (the parent's tails: four copies of
# ``[66, 3, 4352]`` a layer), and no ``[128, 4352]`` / ``[128, 4096]`` row
# array is turned tokens-minor and back.

def _entry_results(text):
    """(op, dtype, dims, bytes) of every instruction of the ENTRY
    computation whose result is one array."""
    import re

    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    size = {"f32": 4, "s32": 4, "bf16": 2}
    out = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(",
            entry, re.M):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        n = size.get(m.group(1), 1)
        for d in dims:
            n *= d
        out.append((m.group(3), m.group(1), dims, n))
    return out


def test_the_mamba_mixer_keeps_one_layout_between_its_products(
        one_chip, monkeypatch):
    from jax.experimental.layout import Format, Layout

    from predictionio_tpu.models import granite_h
    from predictionio_tpu.ops import granite_h_kernels, sambay_kernels

    def shape(x):
        at = one_chip if x.ndim < 2 else Format(
            Layout(major_to_minor=tuple(range(x.ndim))), one_chip)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=at)

    cfg = granite_h.GraniteHConfig(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_attention_heads=32, num_key_value_heads=8, head_dim=64,
        layer_types=("mamba", "attention"), mamba_n_heads=64,
        mamba_d_head=64, mamba_d_state=128)
    # the code asks the backend, which is the CPU here: steer it to the
    # compiled kernels, as on the chip
    for module in (granite_h_kernels, sambay_kernels):
        monkeypatch.setattr(module, "pallas_supported", lambda: True)
    params = jax.eval_shape(lambda: granite_h.cast_for_serving(
        granite_h.init_params(cfg, jax.random.PRNGKey(0))))
    state = jax.eval_shape(
        lambda: granite_h.state_layout(cfg, 128)["allocate"](66, 2900))
    state["table"] = jax.ShapeDtypeStruct((33, granite_h.TABLE_LEN),
                                          jnp.int32)
    step = granite_h.GraniteHStep(cfg)

    class Cache:
        page_size = 128
    sizes = granite_h.vector_sizes(128, 32, step.shapes(128, 32, Cache))
    text = step.program(Cache, 128, 32, 10).lower(
        jax.tree_util.tree_map(shape, params),
        jax.tree_util.tree_map(shape, state),
        shape(jax.ShapeDtypeStruct((sum(sizes),), jnp.int32))
    ).compile().as_text()
    # the names ssd_update_ms and gqa_attn_ms read
    assert "granite_h_ssd_update" in text
    assert "granite_h_gqa_attention" in text
    results = _entry_results(text)
    assert len(results) > 100
    head_major = [r for r in results if r[1] == "f32" and r[3] >= 1 << 20
                  and r[2][-2:] == (64, 64)]
    assert not head_major, head_major
    relayouts = [r for r in results if r[0] in ("copy", "reshape")]
    of_the_pool = [r for r in relayouts if r[2][:1] == (66,)]
    assert not of_the_pool, of_the_pool
    of_the_rows = [r for r in relayouts if r[1] == "f32"
                   and r[2] in ((128, 4352), (128, 4096), (128, 8512))]
    assert not of_the_rows, of_the_rows
    # what is left is the attention layer's (its query rows and output)
    assert sum(r[3] for r in relayouts) < 40e6, relayouts


# The ALS gather's step (PERF.md §6, PR 36): XLA:TPU keeps a gather's
# operand in VMEM (memory space 1 of the compiled text) while its
# physical bytes, 128 lanes a row, fit 112 MiB.  These hold the rule's
# mark (``pallas_kernels.gather_table_pack``) to what the compiler does,
# and the packed view to bringing als-netflix-r64's user table under it.

def _gather_operands(compiled_text):
    """(declaration of the table a gather reads) for every gather."""
    import re

    decl = dict(re.findall(
        r"(%[\w.\-]+) = (\w+\[[\d,]*\]\{[^}]*\}) parameter", compiled_text))
    return [decl[m.group(1)] for m in re.finditer(
        r" gather\((%[\w.\-]+), ", compiled_text)]


@pytest.mark.parametrize("rows_past_the_mark,form,fast", [
    (0, "plain", True),            # 458,752 rows: the last that fit
    (64, "plain", False),          # the plain gather's step
    (64, "rows", True),            # ... which the packed view lies under
    (21_437, "rows", True),        # als-netflix-r64's 480,189 users
    (458_752, "rows", True),       # 917,504 rows: the view's own reach
    (541_248, "rows", False),      # 1,000,000: past it, gathered as it is
], ids=["at-the-mark", "past-it-plain", "past-it", "netflix-users",
        "view-reach", "past-every-view"])
def test_gather_operand_lies_in_vmem_where_the_rule_says(
        one_chip, rows_past_the_mark, form, fast):
    from predictionio_tpu.models import als
    from predictionio_tpu.ops import pallas_kernels

    rank, dtype = 64, jnp.dtype(jnp.bfloat16)
    n_rows = pallas_kernels._GATHER_FAST_TABLE_BYTES // (128 * 2) \
        + rows_past_the_mark
    gather = als._gather_rows if form == "rows" else (
        lambda table, idx, dt: table.astype(dt)[idx])
    text = jax.jit(lambda table, idx: gather(table, idx, dtype)).lower(
        jax.ShapeDtypeStruct((n_rows, rank), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((2048, 512), jnp.int32,
                             sharding=one_chip)).compile().as_text()
    (table,) = _gather_operands(text)
    assert ("S(1)" in table) == fast, table
    if form == "rows":
        pack = pallas_kernels.gather_table_pack(n_rows, rank, 2)
        assert (pack is not None) == fast
        # the operand is the view's shape exactly where the view engages
        assert table.startswith(
            f"bf16[{-(-n_rows // 2)},128]" if pack == 2
            else f"bf16[{n_rows},64]")


# The item side's step of als-netflix-r64 (PERF.md §6, PR 41/42): the packed
# view's 128-lane rows go from the gather straight into the sparse gram
# kernel, which keeps each slot's half itself.  XLA's pass between the
# two (``slice_select_fusion``: a read and a write of the gathered rows in
# HBM) is not in the program; the gather's operand keeps its place in
# VMEM; a view the kernel does not take (rank 10: twelve parts that are
# no whole sublane tiles once transposed) compiles through the pass, as
# before.

def _side_step_text(one_chip, n_src, rank):
    from predictionio_tpu.models import als

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    r, l = 2048, 512            # a chunk of the cell's item side
    als._side_step.clear_cache()
    try:
        return als._side_step.lower(
            shape((r, l), jnp.int32), shape((r, l), jnp.float32),
            shape((r, l), jnp.bool_), shape((r,), jnp.int32),
            shape((17_770, rank), jnp.float32),
            shape((n_src, rank), jnp.float32),
            shape((), jnp.float32), shape((), jnp.float32), implicit=False,
            use_pallas=True, gram_dtype="bfloat16",
            solver="lu").compile().as_text()
    finally:
        als._side_step.clear_cache()


def _copies(text):
    import re

    return len(re.findall(r" = \S+ copy\(", text))


@pytest.mark.parametrize("n_src,rank,in_kernel", [
    (480_189, 64, True), (1_000_000, 10, False)],
    ids=["netflix-items", "rank-10-view"])
def test_packed_rows_reach_the_gram_kernel_with_no_pass_between(
        one_chip, monkeypatch, n_src, rank, in_kernel):
    from predictionio_tpu.models import als
    from predictionio_tpu.ops import pallas_kernels

    pack = pallas_kernels.gather_table_pack(n_src, rank, 2)
    assert pack == 128 // rank > 1
    assert pallas_kernels.gram_takes_packed(rank, pack) == in_kernel
    # the code asks the backend, which is the CPU here: steer it to the
    # compiled kernels, as on the chip
    monkeypatch.setattr(als, "pallas_supported", lambda: True)
    text = _side_step_text(one_chip, n_src, rank)
    # the names als_gram_roofline and als_solve_roofline read
    assert "fused_gram_vector_pallas" in text
    assert "ridge_solve_lu_pallas" in text
    (table,) = _gather_operands(text)
    assert table.startswith(f"bf16[{-(-n_src // pack)},{pack * rank}]")
    assert "S(1)" in table, table
    halved = f"bf16[2048,512,{rank}]"       # the rows after XLA's pass
    if not in_kernel:
        assert "select_fusion" in text and halved in text
        return
    assert "slice_select_fusion" not in text and halved not in text
    assert "bf16[2048,512,128]" in text
    # against the same step with the pass in it (the parent's program)
    monkeypatch.setattr(als, "gram_takes_packed", lambda rank, pack: False)
    parent = _side_step_text(one_chip, n_src, rank)
    assert "slice_select_fusion" in parent and halved in parent
    assert _copies(text) <= _copies(parent)
