"""The main path's kernels and the full-width train step COMPILE for a
TPU v5e — asked of the installed TPU compiler with a described
``v5e:2x2`` and no chip attached.

Interpret mode (every other test of these kernels) cannot see what this
sees: a slice off the (8, 128) tiling, more VMEM than a kernel may use,
a program that does not fit 16 GB.  Nothing runs here, so nothing is
said about results or times — that is ``chip_smoke.py``'s work.

Form (on-chip-measurement guide, section 2): the topology is described
inside a module-scoped fixture that skips where it cannot be described,
never while a module is imported and never ``autouse``; every compile
is made in the test's own process (the worker that describes the
topology holds libtpu until it exits); the persistent cache is off
around it (an entry compiled for a described chip cannot be read back).
``ops/pallas_mode.interpret()`` reads the default backend — the CPU
here — so the tests steer it themselves.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from milnce_tpu.ops import pallas_mode

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # The TPU compiler works on every core it finds, for minutes at the
    # full-width step; the other workers' tests include timing-sensitive
    # ones (decode watchdogs of 0.3 s, step-time spike detectors) that
    # fail when starved.  The compiler's threads are started from here
    # on and inherit this thread's CPU mask: keep them on three cores.
    had = os.sched_getaffinity(0)
    os.sched_setaffinity(0, set(sorted(had)[:3]))
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        os.sched_setaffinity(0, had)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compiled_kernels_and_no_cache(monkeypatch):
    """This file's tests only (the fixture lives here, not in conftest):
    kernels lower through Mosaic instead of the interpreter, and the
    persistent compilation cache is off."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, compiled.as_text()


def _shape(one_chip, dims, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


# --------------------------------------------------------------------------
# soft-DTW: every layout the dispatch rule can pick, forward and backward
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bsz,n,m", [
    (128, 17, 15),          # batch-on-lanes (the reference's preset)
    (1024, 32, 32),         # batch-on-lanes, 8 lane blocks (SDTW_3 regime)
    (32, 256, 256),         # sublane-batch, multi-block grid
    (4, 1024, 1024),        # tables past the VMEM budget: chunked stream
], ids=["lanes-128x17x15", "lanes-1024x32x32", "multiblock-32x256x256",
        "chunked-4x1024x1024"])
def test_softdtw_pallas_compiles_forward_and_backward(one_chip, bsz, n, m):
    from milnce_tpu.ops import softdtw_pallas as sp

    if (bsz, n, m) == (4, 1024, 1024):
        assert not sp._table_fits_vmem(n, m), "shape no longer chunked"

    def value_and_grad(D):
        return jax.value_and_grad(
            lambda d: jnp.sum(sp.softdtw_pallas(d, 0.1)))(D)

    _, text = _compile(value_and_grad, _shape(one_chip, (bsz, n, m)))
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------
# chunked MIL-NCE stream kernel (never compiled for a chip before PR 22)
# --------------------------------------------------------------------------

def _milnce_stream_grads(chunk):
    from milnce_tpu.ops.milnce_pallas import milnce_stream_pallas

    def f(v, t, va, ta):
        row, col = milnce_stream_pallas(v, t, va, ta, chunk)
        return jnp.sum(row) + jnp.sum(col)

    return jax.value_and_grad(f, argnums=(0, 1, 2, 3))


@pytest.mark.parametrize("bg,chunk", [(128, 8), (512, 8)],
                         ids=["Bg128-16chunks", "Bg512-64chunks"])
def test_milnce_pallas_compiles_forward_and_backward(one_chip, bg, chunk):
    from milnce_tpu.ops.milnce_pallas import prefers_pallas

    b, k, d = 128, 5, 512
    assert prefers_pallas(b, bg, k, d, chunk), "auto would not pick this"
    _, text = _compile(
        _milnce_stream_grads(chunk),
        _shape(one_chip, (b, d)), _shape(one_chip, (b * k, d)),
        _shape(one_chip, (bg, d)), _shape(one_chip, (bg * k, d)))
    assert "tpu_custom_call" in text


def test_milnce_pallas_outside_vmem_budget_names_the_knob(one_chip):
    """b=128, Bg=512, K=5, D=512 at the default chunk (512): the TPU
    compiler refuses the backward (``RESOURCE_EXHAUSTED ... vmem``) from
    deep inside the step compile.  ``backend='auto'`` never picks it; an
    explicit ``loss.milnce_backend=pallas`` now fails at trace time
    naming the knob and the shape."""
    from milnce_tpu.losses.milnce_chunked import milnce_default_chunk

    b, bg, k, d = 128, 512, 5, 512
    chunk = milnce_default_chunk(b, k, bg)
    assert chunk == 512
    with pytest.raises(ValueError) as exc_info:
        _compile(
            _milnce_stream_grads(chunk),
            _shape(one_chip, (b, d)), _shape(one_chip, (b * k, d)),
            _shape(one_chip, (bg, d)), _shape(one_chip, (bg * k, d)))
    msg = str(exc_info.value)
    assert "loss.milnce_backend=pallas" in msg
    assert "b_global=512" in msg and "loss.milnce_chunk=512" in msg


# --------------------------------------------------------------------------
# the full-width single-chip train step, at chip_smoke.py's batch
# --------------------------------------------------------------------------

def test_full_width_train_step_fits_one_v5e(topo):
    """The jitted ``make_train_step`` of the ``full`` preset's model
    (9 blocks, 512/66250/300/2048) in bfloat16 at 32f@224, K=5, 20 words
    and the smoke's batch, lowered from ``jax.eval_shape`` shapes onto a
    one-device mesh of the described topology: it compiles, and what it
    needs on the device stays under a v5e's 16 GB."""
    import chip_smoke
    from milnce_tpu.config import full_preset
    from milnce_tpu.models.build import build_model
    from milnce_tpu.train.schedule import build_schedule
    from milnce_tpu.train.state import build_optimizer, create_train_state
    from milnce_tpu.train.step import make_train_step

    cfg = full_preset()
    cfg.model.dtype = "bfloat16"
    sizes = chip_smoke.FULL_SIZES
    batch, frames, size = sizes.batch, sizes.frames, sizes.size
    k, words = sizes.candidates, sizes.words
    assert (frames, size, k, words) == (32, 224, 5, 20)
    model = build_model(cfg.model)
    optimizer = build_optimizer(cfg.optim, build_schedule(cfg.optim, 1000))

    def init_state(key):
        variables = model.init(
            key, jnp.zeros((2, frames, size, size, 3), jnp.float32),
            jnp.zeros((2 * k, words), jnp.int32))
        return create_train_state(variables, optimizer)

    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        jax.eval_shape(init_state, jax.random.PRNGKey(0)))
    step = make_train_step(model, optimizer, mesh, finite_guard=True)
    compiled = step.lower(
        state,
        jax.ShapeDtypeStruct((batch, frames, size, size, 3), jnp.uint8,
                             sharding=data),
        jax.ShapeDtypeStruct((batch * k, words), jnp.int32, sharding=data),
        jax.ShapeDtypeStruct((batch,), jnp.float32,
                             sharding=data)).compile()
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < need < V5E_HBM_BYTES, (
        f"full-width step at batch {batch} needs {need / 1e9:.2f} GB")


# --------------------------------------------------------------------------
# the expert layers' grouped product at the served tower's shapes, every rung
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [16, 32, 64])
def test_grouped_matmul_compiles_at_each_rungs_turn(one_chip, rows):
    """``ops/grouped_matmul.py`` at what ``held_expert_sum`` hands it in
    the ``rows``-row program of ``query-text-axk1-c64``: a turn of
    ``rows x 32 / 4`` pairs against 12 held experts' 7168 x 2048 (gate,
    up) and 2048 x 7168 (down) bfloat16 matrices, at the tile the rule
    gives: Mosaic takes the blocks (4 MB of a matrix a step, double-
    buffered, beside a float32 accumulator) and the dynamic grid bound."""
    from milnce_tpu.ops import grouped_matmul as gm

    turn, held, hidden, width = rows * 32 // 4, 12, 7168, 2048
    sizes = _shape(one_chip, (held,), jnp.int32)
    for k, n, out_dtype in ((hidden, width, jnp.bfloat16),
                            (width, hidden, jnp.float32)):
        tm, tk, tn = gm.tiling(turn, k, n, jnp.bfloat16)
        assert tm == 128 and turn % tm == 0
        assert tk * tn * 2 <= gm.BLOCK_BYTES and k % tk == 0 and n % tn == 0
        _, text = _compile(
            lambda r, s, g: gm.grouped_matmul(r, s, g, out_dtype=out_dtype),
            _shape(one_chip, (turn, k), jnp.bfloat16),
            _shape(one_chip, (held, k, n), jnp.bfloat16), sizes)
        assert "tpu_custom_call" in text and "grouped_matmul" in text


# --------------------------------------------------------------------------
# the index shard's scan + top-k at the cells' shards and rungs
# --------------------------------------------------------------------------

@pytest.mark.parametrize("queries,rows", [(64, 3_000_000), (16, 1_062_500),
                                          (4, 1_062_500)])
def test_scan_topk_compiles_at_the_cells_shapes(one_chip, queries, rows):
    """``ops/scan_topk.py`` over the shards the cells scan (3 M rows on
    ``query-text-c64``, 1,062,500 on the other three) at the top and
    bottom rungs of both ladders: Mosaic takes the tile the rule gives
    (8 MB of the index a step, double-buffered, ragged at the end) and
    the program holds no (Q, rows) scores."""
    from milnce_tpu.ops import scan_topk as st

    compiled, text = _compile(
        lambda q, c, v: st.scan_topk(q, c, v, 10),
        _shape(one_chip, (queries, 512)), _shape(one_chip, (rows, 512)),
        _shape(one_chip, (1,), jnp.int32))
    assert "tpu_custom_call" in text and "scan_topk" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_top_k_program_ranks_without_a_topk_over_the_shard(topo):
    """``make_topk_fn`` on one described chip at ``query-text-c64``'s
    shape: the kernel is there, and neither the (64, 3 M) scores nor a
    ``TopK`` over them is."""
    from milnce_tpu.serving.index import make_topk_fn

    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    rows_sh, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    text = make_topk_fn(mesh, "data", 10).lower(
        jax.ShapeDtypeStruct((3_000_000, 512), jnp.float32, sharding=rows_sh),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=rows_sh),
        jax.ShapeDtypeStruct((64, 512), jnp.float32, sharding=rep),
    ).compile().as_text()
    assert "tpu_custom_call" in text and "scan_topk" in text
    assert "3000000]" not in text.replace("f32[3000000,512]", "")


# --------------------------------------------------------------------------
# the language-model sentence tower at its published widths, top rung
# --------------------------------------------------------------------------

def test_axk1_sentence_tower_fits_beside_what_the_cell_holds(topo):
    """``make_text_embed_fn``'s program (``text_lm_tower``) for the
    ``text_lm`` group that ``benchmarks/drivers/serve_lm.py`` makes from
    ``benchmarks/configs/s3dg-axk1-text-32f224.json`` — hidden 7168, MLA
    ranks 1536/512, 192-wide router, 12 experts held, 8 layers, bfloat16 —
    at the 64-row rung of 32 tokens, lowered from ``jax.eval_shape``
    shapes onto a one-device mesh of the described topology.  It compiles
    (the grouped expert products are ``ops/grouped_matmul.py``'s kernel,
    lowered through Mosaic as this file's other kernels are: a
    ``tpu_custom_call`` named ``grouped_matmul``, and no ``ragged-dot``
    left), and what it keeps and needs on the device leaves room, inside the
    16,909,336,064 bytes the chip's ``memory_stats()`` gives as
    ``bytes_limit`` (PERF.md), for what the cell holds beside it: while
    serving, the index shard and two (64, rows) float32 score blocks; at
    boot, the video tower's 64-row warm-up (4.95 + 0.37 GB, PR 28's
    compile of it for this topology)."""
    from benchmarks import harness
    from benchmarks.drivers import serve_lm
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_lm
    from milnce_tpu.models.build import build_model
    from milnce_tpu.train.step import make_text_embed_fn

    cell = harness.load_json("benchmarks/configs/s3dg-axk1-text-32f224.json")
    cfg = parse_cli(serve_lm.text_lm_flags(cell) + [
        "--model.text_tower", "lm", "--model.dtype", "bfloat16"])
    rows, words = cell["serve"]["max_batch"], cell["data"]["max_words"]
    assert (cfg.text_lm.hidden_size, cfg.text_lm.n_routed_experts,
            cfg.text_lm.experts_held, rows, words) == (7168, 192, 12, 64, 32)
    model = build_model(cfg.model, text_lm=cfg.text_lm)
    tower = text_lm.TextLM(model.text_lm, embd_dim=512, dtype=jnp.bfloat16)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = jax.eval_shape(tower.init, jax.random.PRNGKey(0),
                            jnp.zeros((rows, words), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=repl),
        {"text_module": shapes})
    held = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert 5.3e9 < held < 5.45e9            # 5.37 B parameters, 10.75 GB
    compiled = make_text_embed_fn(model, mesh).lower(
        {"params": params},
        jax.ShapeDtypeStruct((rows, words), jnp.int32,
                             sharding=data)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    limit = 16_909_336_064
    index = cell["index"]["rows"] * cell["index"]["dim"] * 4
    scores = 2 * rows * cell["index"]["rows"] * 4
    video_warm_up = 4.95e9 + 0.37e9
    assert need + index + scores < limit, (
        f"tower {need / 1e9:.2f} GB + index {index / 1e9:.2f} GB + scores "
        f"{scores / 1e9:.2f} GB")
    assert mem.argument_size_in_bytes + video_warm_up < limit, (
        f"tower's weights {mem.argument_size_in_bytes / 1e9:.2f} GB beside "
        "the video tower's warm-up")


# --------------------------------------------------------------------------
# the hybrid sentence tower at its published widths, top rung
# --------------------------------------------------------------------------

def test_granite4h_sentence_tower_fits_beside_what_the_cell_holds(topo):
    """``make_text_embed_fn``'s program (``text_hybrid_tower``) for the
    ``text_hybrid`` group that ``benchmarks/drivers/serve_tower.py`` makes
    from ``benchmarks/configs/s3dg-granite4h-text-32f224.json`` — hidden
    4096, nine Mamba-2 layers (128 heads x 64, state 128, chunks of 256)
    and one NoPE attention layer (32 query heads over 8), a 72-wide router,
    36 experts held, bfloat16 — at the 16-row rung of 512 tokens, lowered
    from ``jax.eval_shape`` shapes onto a one-device mesh of the described
    topology.  It compiles (the grouped expert products are
    ``ops/grouped_matmul.py``'s kernel at (4096, 768) and (768, 4096)
    matrices), and its arguments and temporaries, with the index shard and
    two (16, rows) float32 score blocks, lie inside the 16,909,336,064
    bytes the chip's ``memory_stats()`` gives as ``bytes_limit``."""
    from benchmarks import harness
    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_hybrid
    from milnce_tpu.models.build import build_model
    from milnce_tpu.train.step import make_text_embed_fn

    cell = harness.load_json(
        "benchmarks/configs/s3dg-granite4h-text-32f224.json")
    cfg = parse_cli(serve_tower.group_flags(cell) + [
        "--model.text_tower", "hybrid", "--model.dtype", "bfloat16"])
    rows, words = cell["serve"]["max_batch"], cell["data"]["max_words"]
    group = cfg.text_hybrid
    assert (group.hidden_size, group.num_local_experts, group.experts_held,
            group.num_hidden_layers, rows, words) == (4096, 72, 36, 10, 16,
                                                      512)
    model = build_model(cfg.model, text_hybrid=group)
    assert model.text_hybrid.layer_types == ("mamba",) * 5 + (
        "attention",) + ("mamba",) * 4
    tower = text_hybrid.TextHybrid(model.text_hybrid, embd_dim=512,
                                   dtype=jnp.bfloat16)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = jax.eval_shape(tower.init, jax.random.PRNGKey(0),
                            jnp.zeros((rows, words), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=repl),
        {"text_module": shapes})
    held = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert 4.7e9 < held < 4.85e9            # 4.76 B parameters, 9.5 GB
    compiled = make_text_embed_fn(model, mesh).lower(
        {"params": params},
        jax.ShapeDtypeStruct((rows, words), jnp.int32,
                             sharding=data)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    assert "text_hybrid/ssd" in text and "text_hybrid_tower" in text
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    limit = 16_909_336_064
    index = cell["index"]["rows"] * cell["index"]["dim"] * 4
    scores = 2 * rows * cell["index"]["rows"] * 4
    print(f"granite4h tower: {held} parameters, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert need + index + scores < limit, (
        f"tower {need / 1e9:.2f} GB + index {index / 1e9:.2f} GB + scores "
        f"{scores / 1e9:.2f} GB")


def test_solar2_sentence_tower_fits_beside_what_the_cell_holds(topo):
    """``make_text_embed_fn``'s program (``text_hybrid_tower``) for the
    ``text_hybrid`` group that ``benchmarks/drivers/serve_tower.py`` makes
    from ``benchmarks/configs/s3dg-solar2-text-32f224.json`` — hidden
    4096, one gated NoPE attention layer (64 query heads over 8, heads of
    128) and three Kimi Delta Attention layers (64 heads of 128, chunks of
    64), a 320-wide sigmoid router, 40 experts of 1280 held beside a
    shared one, the whole 196,608-row table, bfloat16 — at the 16-row rung
    of 512 tokens.  It compiles for a described v5e (the delta rule is
    ``ops/kda.py``'s chunked form, the expert products
    ``ops/grouped_matmul.py``'s kernel), and its arguments and
    temporaries, with the index shard and two (16, rows) float32 score
    blocks, lie inside the chip's ``bytes_limit``; at boot its weights and
    the index stand beside the video tower's warm-up."""
    from benchmarks import harness
    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_hybrid
    from milnce_tpu.models.build import build_model
    from milnce_tpu.train.step import make_text_embed_fn

    cell = harness.load_json("benchmarks/configs/s3dg-solar2-text-32f224.json")
    cfg = parse_cli(serve_tower.group_flags(cell) + [
        "--model.text_tower", "hybrid", "--model.dtype", "bfloat16"])
    rows, words = cell["serve"]["max_batch"], cell["data"]["max_words"]
    group = cfg.text_hybrid
    assert (group.hidden_size, group.num_local_experts, group.experts_held,
            group.num_hidden_layers, group.kda_num_heads, group.head_dim,
            rows, words) == (4096, 320, 40, 4, 64, 128, 16, 512)
    model = build_model(cfg.model, text_hybrid=group)
    assert model.text_hybrid.layer_types == ("attention", "kda", "kda",
                                             "kda")
    tower = text_hybrid.TextHybrid(model.text_hybrid, embd_dim=512,
                                   dtype=jnp.bfloat16)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = jax.eval_shape(tower.init, jax.random.PRNGKey(0),
                            jnp.zeros((rows, words), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=repl),
        {"text_module": shapes})
    held = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert held == 3_914_428_992            # 7.83 GB
    compiled = make_text_embed_fn(model, mesh).lower(
        {"params": params},
        jax.ShapeDtypeStruct((rows, words), jnp.int32,
                             sharding=data)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    for scope in cell["bench"]["scopes"]:
        assert scope in text, scope
    assert "text_hybrid_tower" in text
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    limit = 16_909_336_064
    index = cell["index"]["rows"] * cell["index"]["dim"] * 4
    scores = 2 * rows * cell["index"]["rows"] * 4
    video_warm_up = 4.95e9 + 0.37e9
    print(f"solar2 tower: {held} parameters, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert need + index + scores < limit, (
        f"tower {need / 1e9:.2f} GB + index {index / 1e9:.2f} GB + scores "
        f"{scores / 1e9:.2f} GB")
    assert mem.argument_size_in_bytes + index + video_warm_up < limit, (
        f"tower's weights {mem.argument_size_in_bytes / 1e9:.2f} GB and the "
        "index beside the video tower's warm-up")


# --------------------------------------------------------------------------
# the block-diffusion sentence tower at its published widths, top rung
# --------------------------------------------------------------------------

def test_sdar_sentence_tower_fits_beside_what_the_cell_holds(topo):
    """``make_text_embed_fn``'s program (``text_dlm_tower``) for the
    ``text_dlm`` group that ``benchmarks/drivers/serve_tower.py`` makes
    from ``benchmarks/configs/s3dg-sdar-text-32f224.json`` — hidden 2048,
    32 query heads over 4 key/value heads of 128, all 128 experts of 768
    held, six layers, the whole 151,936-row table and head, bfloat16 — at
    the 64-row rung of 32 slots: ONE program that prefills, loops four
    blocks of denoise passes and a commit pass over a 48-position cache,
    and embeds.  It compiles for a described v5e (the grouped expert
    products are ``ops/grouped_matmul.py``'s kernel at (2048, 768) and
    (768, 2048) matrices over 128 groups, a turn of every pair), and its
    arguments and temporaries, with the index shard and two (64, rows)
    float32 score blocks, lie inside the 16,909,336,064 bytes the chip's
    ``memory_stats()`` gives as ``bytes_limit``; at boot its weights stand
    beside the video tower's 64-row warm-up (4.95 + 0.37 GB, PR 28)."""
    from benchmarks import harness
    from benchmarks.drivers import serve_tower
    from milnce_tpu.config import parse_cli
    from milnce_tpu.models import text_dlm
    from milnce_tpu.models.build import build_model
    from milnce_tpu.train.step import make_text_embed_fn

    cell = harness.load_json("benchmarks/configs/s3dg-sdar-text-32f224.json")
    cfg = parse_cli(serve_tower.group_flags(cell) + [
        "--model.text_tower", "dlm", "--model.dtype", "bfloat16"])
    rows, words = cell["serve"]["max_batch"], cell["data"]["max_words"]
    group = cfg.text_dlm
    assert (group.hidden_size, group.num_experts, group.experts_held,
            group.num_hidden_layers, group.vocab_size, group.expand_blocks,
            rows, words) == (2048, 128, 128, 6, 151936, 4, 64, 32)
    model = build_model(cfg.model, text_dlm=group)
    tower = text_dlm.TextDLM(model.text_dlm, embd_dim=512,
                             dtype=jnp.bfloat16)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = jax.eval_shape(tower.init, jax.random.PRNGKey(0),
                            jnp.zeros((rows, words), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=repl),
        {"text_module": shapes})
    held = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert held == 4_362_104_320            # 8.72 GB
    compiled = make_text_embed_fn(model, mesh).lower(
        {"params": params},
        jax.ShapeDtypeStruct((rows, words), jnp.int32,
                             sharding=data)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    for scope in cell["bench"]["scopes"]:
        assert scope in text, scope
    assert "text_dlm_tower" in text
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    limit = 16_909_336_064
    index = cell["index"]["rows"] * cell["index"]["dim"] * 4
    scores = 2 * rows * cell["index"]["rows"] * 4
    video_warm_up = 4.95e9 + 0.37e9
    print(f"sdar tower: {held} parameters, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert need + index + scores < limit, (
        f"tower {need / 1e9:.2f} GB + index {index / 1e9:.2f} GB + scores "
        f"{scores / 1e9:.2f} GB")
    assert mem.argument_size_in_bytes + index + video_warm_up < limit, (
        f"tower's weights {mem.argument_size_in_bytes / 1e9:.2f} GB and the "
        "index beside the video tower's warm-up")
