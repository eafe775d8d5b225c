"""Edge-tier quantization subsystem (ISSUE 19): int8 calibrated
towers, the distilled text student, and heterogeneous replica classes.

Four regression fences:

- quantize -> export -> restore round-trips BIT-EXACTLY (int8 leaves
  and scales), and the v1 loader refuses the v2 artifact loudly;
- both edge artifacts (int8, student) boot through the serving engine
  and answer with recall@10 inside the stated degradation budgets
  against the f32 tower on a tiny synthetic corpus;
- a mixed ReplicaPool routes class-pinned requests STRICTLY (an edge
  pin never silently lands on an f32 replica, and vice versa);
- the NUMERICS.md readiness-verdict parser keeps reading the committed
  table the calibration defaults are seeded from.
"""

import os

import numpy as np
import pytest

_WORDS = 6
_FRAMES, _SIZE = 4, 32
_VIDEO_SHAPE = (_FRAMES, _SIZE, _SIZE, 3)
_CORPUS = 24

# Edge-tier recall@10 degradation budgets (SERVING.md "Edge tier"):
# each edge class's top-10 rankings against the f32 tower's on the
# tiny synthetic corpus must keep at least this mean overlap.  The
# committed serve_bench --tier-class records pin the same quantity at
# serving scale; obs_report gates drift.
INT8_RECALL_BUDGET = 0.80
STUDENT_RECALL_BUDGET = 0.50


@pytest.fixture(scope="module")
def tiny():
    """Tiny teacher: model + frozen f32 serving tree."""
    import jax
    import jax.numpy as jnp

    from milnce_tpu.config import ModelConfig
    from milnce_tpu.models.build import build_model

    mcfg = ModelConfig(embedding_dim=16, vocab_size=128,
                       word_embedding_dim=8, text_hidden_dim=16,
                       inception_blocks=1)
    model = build_model(mcfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1,) + _VIDEO_SHAPE),
                           jnp.zeros((1, _WORDS), jnp.int32))
    frozen = {"params": variables["params"],
              "batch_stats": variables.get("batch_stats", {})}
    return dict(mcfg=mcfg, model=model, frozen=frozen)


@pytest.fixture(scope="module")
def f32_dir(tiny, tmp_path_factory):
    from milnce_tpu.serving.export import export_inference_checkpoint

    out = str(tmp_path_factory.mktemp("f32_export"))
    export_inference_checkpoint(
        out, tiny["frozen"]["params"], tiny["frozen"]["batch_stats"],
        tiny["mcfg"], max_words=_WORDS, video_shape=_VIDEO_SHAPE)
    return out


@pytest.fixture(scope="module")
def calibrated(tiny):
    """The full offline pass: quantized tree + calibration metadata."""
    from milnce_tpu.quant.calibrate import calibrate_and_quantize

    rng = np.random.default_rng(3)
    video = rng.integers(0, 255, (2,) + _VIDEO_SHAPE).astype(np.float32)
    tokens = rng.integers(1, 128, (4, _WORDS)).astype(np.int32)
    qvars, calibration = calibrate_and_quantize(
        tiny["model"], tiny["frozen"], video_batches=[video],
        text_batches=[tokens])
    return dict(qvars=qvars, calibration=calibration)


@pytest.fixture(scope="module")
def quant_dir(tiny, calibrated, tmp_path_factory):
    from milnce_tpu.serving.export import export_quantized_checkpoint

    out = str(tmp_path_factory.mktemp("quant_export"))
    export_quantized_checkpoint(
        out, calibrated["qvars"], tiny["mcfg"], max_words=_WORDS,
        video_shape=_VIDEO_SHAPE, calibration=calibrated["calibration"])
    return out


@pytest.fixture(scope="module")
def student(tiny):
    from milnce_tpu.quant.distill import (build_student_variables,
                                          distill_text_student,
                                          student_model_config)

    sparams, sinfo = distill_text_student(
        tiny["model"], tiny["frozen"], max_words=_WORDS, steps=80,
        batch_size=16)
    scfg = student_model_config(tiny["mcfg"], sinfo["hidden_dim"])
    svars = build_student_variables(tiny["frozen"], sparams)
    return dict(scfg=scfg, svars=svars, sinfo=sinfo)


@pytest.fixture(scope="module")
def student_dir(student, tmp_path_factory):
    from milnce_tpu.serving.export import export_inference_checkpoint

    out = str(tmp_path_factory.mktemp("student_export"))
    export_inference_checkpoint(
        out, student["svars"]["params"], student["svars"]["batch_stats"],
        student["scfg"], max_words=_WORDS, video_shape=_VIDEO_SHAPE,
        source="distilled text student (quant/distill.py)")
    return out


def _mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("data",))


# ---------------------------------------------------------------------------
# quantize: scheme + round-trip
# ---------------------------------------------------------------------------

class TestQuantize:
    def test_int8_where_quantizable_f32_elsewhere(self, calibrated):
        import jax

        qvars = calibrated["qvars"]
        scales = qvars["quant_scales"]
        assert scales, "nothing was quantized"
        flat = jax.tree_util.tree_leaves_with_path(qvars["params"])
        n_int8 = sum(np.asarray(leaf).dtype == np.int8
                     for _, leaf in flat)
        assert n_int8 == len(scales)
        for _, leaf in jax.tree_util.tree_leaves_with_path(
                qvars["batch_stats"]):
            assert np.asarray(leaf).dtype != np.int8

    def test_dequant_error_bounded_by_half_scale(self, tiny):
        """Symmetric int8 round-to-nearest: |x - q*s| <= s/2 per
        element (per-channel: that channel's scale)."""
        from milnce_tpu.quant.quantize import (quantize_array)

        rng = np.random.default_rng(0)
        arr = rng.standard_normal((12, 8)).astype(np.float32)
        arr[:, 0] *= 40.0                  # an outlier channel
        for per_channel in (False, True):
            q, scale = quantize_array(arr, per_channel=per_channel)
            assert q.dtype == np.int8
            err = np.abs(arr - q.astype(np.float32) * scale)
            assert (err <= np.asarray(scale) * 0.5 + 1e-7).all()

    def test_per_channel_verdicts_follow_readiness_rule(self, tiny):
        from milnce_tpu.quant.quantize import (
            per_channel_keys_from_weights, weight_readiness_row)

        keys = per_channel_keys_from_weights(tiny["frozen"]["params"])
        # the rule and the key set must agree leaf by leaf
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(
            tiny["frozen"]["params"])
        for path, leaf in flat:
            arr = np.asarray(leaf)
            if arr.ndim < 2:
                continue
            key = "params/" + "/".join(
                getattr(p, "key", str(p)) for p in path)
            row = weight_readiness_row(key, arr)
            assert (key in keys) == row["per_channel"], key


# ---------------------------------------------------------------------------
# export format v2
# ---------------------------------------------------------------------------

class TestQuantExport:
    def test_round_trip_is_bit_exact(self, calibrated, quant_dir):
        import jax

        from milnce_tpu.serving.export import load_quantized_checkpoint

        meta, loaded = load_quantized_checkpoint(quant_dir)
        qvars = calibrated["qvars"]
        a = jax.tree_util.tree_leaves_with_path(qvars["params"])
        b = dict(jax.tree_util.tree_leaves_with_path(loaded["params"]))
        assert len(a) == len(b)
        for path, leaf in a:
            orig = np.asarray(leaf)
            back = np.asarray(b[path])
            assert orig.dtype == back.dtype, path
            assert np.array_equal(orig, back), path
        assert sorted(loaded["quant_scales"]) == sorted(
            qvars["quant_scales"])
        for key, scale in qvars["quant_scales"].items():
            assert np.array_equal(np.asarray(scale, np.float32),
                                  loaded["quant_scales"][key]), key

    def test_metadata_contract(self, quant_dir):
        from milnce_tpu.serving.export import (ARRAYS_FILE,
                                               QUANT_FORMAT_VERSION,
                                               SCALES_PREFIX,
                                               read_export_metadata)

        meta = read_export_metadata(quant_dir)
        assert meta["format_version"] == QUANT_FORMAT_VERSION
        quant = meta["quant"]
        assert quant["scheme"] == "symmetric-int8"
        assert quant["n_quantized"] > 0
        # calibration block rode along (quality stats + ranges)
        assert quant["calibration"]["quality"]["text_cosine_mean"] > 0.9
        # dtype manifest covers every shipped array, int8 where the
        # scales say a leaf was quantized, f32 for the scales themselves
        dtypes = meta["array_dtypes"]
        with np.load(os.path.join(quant_dir, ARRAYS_FILE)) as z:
            assert sorted(dtypes) == sorted(z.files)
        for key in quant["per_channel"]:
            assert dtypes[key] == "int8", key
        assert all(v == "float32" for k, v in dtypes.items()
                   if k.startswith(SCALES_PREFIX + "/"))

    def test_v1_loader_rejects_v2_with_hint(self, quant_dir):
        from milnce_tpu.serving.export import load_inference_checkpoint

        with pytest.raises(ValueError, match="load_quantized_checkpoint"):
            load_inference_checkpoint(quant_dir)

    def test_dtype_override_refused_on_quant_exports(self, quant_dir):
        from milnce_tpu.serving.engine import InferenceEngine

        with pytest.raises(ValueError, match="dtype override"):
            InferenceEngine.from_export(quant_dir, _mesh(), max_batch=8,
                                        dtype="bfloat16")


# ---------------------------------------------------------------------------
# serving: both edge artifacts boot and stay inside the recall budgets
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(f32_dir, quant_dir, student_dir):
    """Rankings per class: engine-from-export -> corpus + query
    embeddings -> top-10 ids (one shared u8 corpus + query pool)."""
    from milnce_tpu.serving.engine import InferenceEngine

    mesh = _mesh()
    rng = np.random.default_rng(11)
    clips = rng.integers(0, 255, (_CORPUS,) + _VIDEO_SHAPE,
                         dtype=np.uint8)
    queries = rng.integers(1, 128, (8, _WORDS)).astype(np.int32)
    out = {}
    for name, export_dir in (("f32", f32_dir), ("int8", quant_dir),
                             ("student", student_dir)):
        engine = InferenceEngine.from_export(export_dir, mesh,
                                             max_batch=16)
        corpus = np.concatenate([engine.embed_video(clips[:16]),
                                 engine.embed_video(clips[16:])])
        text = engine.embed_text(queries)
        out[name] = {
            "top10": np.argsort(-(text @ corpus.T), axis=1)[:, :10],
            "recompiles": engine.recompiles(),
            "embed_dim": text.shape[-1],
        }
    return out


def _recall(idx, base) -> float:
    return float(np.mean([len(set(a) & set(b)) / idx.shape[1]
                          for a, b in zip(idx, base)]))


class TestEdgeServing:
    def test_all_classes_boot_with_zero_recompiles(self, served):
        for name, r in served.items():
            assert r["recompiles"] == 0, name
            assert r["embed_dim"] == 16, name    # shared embedding space

    def test_int8_recall_budget(self, served):
        recall = _recall(served["int8"]["top10"], served["f32"]["top10"])
        assert recall >= INT8_RECALL_BUDGET, recall

    def test_student_recall_budget(self, served):
        recall = _recall(served["student"]["top10"],
                         served["f32"]["top10"])
        assert recall >= STUDENT_RECALL_BUDGET, recall

    def test_student_keeps_teacher_word_table(self, tiny, student):
        teacher = np.asarray(
            tiny["frozen"]["params"]["text_module"]["word_embd"]
            ["embedding"])
        svars = student["svars"]
        mine = np.asarray(
            svars["params"]["text_module"]["word_embd"]["embedding"])
        assert np.array_equal(teacher, mine)
        assert student["sinfo"]["hidden_dim"] < \
            student["sinfo"]["teacher_hidden_dim"]


# ---------------------------------------------------------------------------
# heterogeneous replica classes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mixed_stack(f32_dir, quant_dir):
    """One f32 + one edge (int8) replica behind one service."""
    from milnce_tpu.serving.index import DeviceRetrievalIndex
    from milnce_tpu.serving.pool import ReplicaPool
    from milnce_tpu.serving.service import RetrievalService

    pool = ReplicaPool.from_export(f32_dir, 1, max_batch=8,
                                   edge_export_dir=quant_dir,
                                   edge_replicas=1)
    rng = np.random.default_rng(5)
    clips = rng.integers(0, 255, (8,) + _VIDEO_SHAPE, dtype=np.uint8)
    corpus_emb = pool.embed_video(clips)
    index = DeviceRetrievalIndex(_mesh(), corpus_emb, k=5,
                                 query_buckets=pool.buckets)
    service = RetrievalService(pool, index)
    yield dict(pool=pool, service=service)
    service.close()
    pool.close()


class TestReplicaClasses:
    def test_pool_reports_both_classes(self, mixed_stack):
        stats = mixed_stack["pool"].stats()
        assert stats["classes"] == {"edge": 1, "f32": 1}

    @pytest.mark.parametrize("cls", ["f32", "edge"])
    def test_class_pinned_embed_serves(self, mixed_stack, cls):
        tokens = np.ones((2, _WORDS), np.int32)
        out = mixed_stack["pool"].embed_text(tokens, cls=cls)
        assert out.shape == (2, 16) and np.isfinite(out).all()

    def test_unknown_class_is_a_loud_error(self, mixed_stack):
        with pytest.raises(ValueError, match="replica class"):
            mixed_stack["pool"].embed_text(np.ones((1, _WORDS), np.int32),
                                           cls="gpu")

    def test_class_routing_is_strict(self, mixed_stack):
        """A pinned dispatch NEVER falls back across classes: with the
        only edge replica excluded, routing fails PoolUnavailable even
        though the f32 replica has capacity."""
        from milnce_tpu.serving.pool import PoolUnavailable

        pool = mixed_stack["pool"]
        (edge_rid,) = [r.rid for r in pool.replicas if r.cls == "edge"]
        with pytest.raises(PoolUnavailable, match="edge"):
            pool._route(cls="edge", exclude=(edge_rid,))

    @pytest.mark.parametrize("cls", ["f32", "edge"])
    def test_service_request_pins_a_class(self, mixed_stack, cls):
        tokens = np.ones((1, _WORDS), np.int32)
        scores, ids = mixed_stack["service"].query_ids(
            tokens, replica_class=cls)
        assert scores.shape == (1, 5) and ids.shape == (1, 5)

    def test_service_unknown_class_is_a_loud_error(self, mixed_stack):
        with pytest.raises(ValueError, match="replica class"):
            mixed_stack["service"].query_ids(
                np.ones((1, _WORDS), np.int32), replica_class="gpu")

    def test_unpooled_service_refuses_class_pins(self, tiny):
        from milnce_tpu.serving.engine import InferenceEngine
        from milnce_tpu.serving.index import DeviceRetrievalIndex
        from milnce_tpu.serving.service import RetrievalService

        mesh = _mesh()
        engine = InferenceEngine(tiny["model"], dict(tiny["frozen"]),
                                 mesh, text_words=_WORDS,
                                 video_shape=_VIDEO_SHAPE, max_batch=8)
        rng = np.random.default_rng(6)
        corpus = engine.embed_video(rng.integers(
            0, 255, (8,) + _VIDEO_SHAPE, dtype=np.uint8))
        index = DeviceRetrievalIndex(mesh, corpus, k=3,
                                     query_buckets=engine.buckets)
        service = RetrievalService(engine, index)
        try:
            with pytest.raises(ValueError, match="pooled"):
                service.query_ids(np.ones((1, _WORDS), np.int32),
                                  replica_class="f32")
        finally:
            service.close()

    def test_contract_mismatch_refused(self, tiny, calibrated, f32_dir,
                                       tmp_path):
        """An edge artifact disagreeing on the serving contract
        (max_words here) must not join the pool."""
        from milnce_tpu.serving.export import export_quantized_checkpoint
        from milnce_tpu.serving.pool import ReplicaPool

        bad = str(tmp_path / "bad_edge")
        export_quantized_checkpoint(
            bad, calibrated["qvars"], tiny["mcfg"],
            max_words=_WORDS + 1, video_shape=_VIDEO_SHAPE)
        with pytest.raises(ValueError, match="serving contract"):
            ReplicaPool.from_export(f32_dir, 1, max_batch=8,
                                    edge_export_dir=bad,
                                    edge_replicas=1)


# ---------------------------------------------------------------------------
# NUMERICS.md verdict parsing (the calibration defaults' seed)
# ---------------------------------------------------------------------------

class TestVerdictParser:
    def test_parses_both_verdict_spellings(self, tmp_path):
        from milnce_tpu.quant.calibrate import read_numerics_verdicts

        report = tmp_path / "NUMERICS.md"
        report.write_text(
            "| layer | shape | absmax | verdict |\n"
            "| --- | --- | --- | --- |\n"
            "| `params/text_module/fc1/kernel` | (8, 16) | 1.2 "
            "| **per-channel** |\n"
            "| `params/conv1/conv/kernel` | (3, 3, 3, 8) | 0.4 "
            "| per-tensor ok |\n")
        verdicts = read_numerics_verdicts(str(report))
        assert verdicts == {"params/text_module/fc1/kernel": True,
                            "params/conv1/conv/kernel": False}

    def test_committed_report_still_parses(self):
        """The committed NUMERICS.md keeps a readable readiness table —
        calibrate_and_quantize seeds its per-channel defaults from it."""
        from milnce_tpu.quant.calibrate import read_numerics_verdicts

        report = os.path.join(os.path.dirname(__file__), os.pardir,
                              "NUMERICS.md")
        verdicts = read_numerics_verdicts(report)
        assert verdicts, "NUMERICS.md lost its quantization-readiness " \
                         "table (regenerate: python scripts/" \
                         "precision_audit.py)"
        assert all(k.startswith("params/") for k in verdicts)

    def test_committed_verdicts_seed_calibration(self, tiny):
        """The whole loop: the COMMITTED report's verdicts must always
        be a usable per-channel default for quantization — a report
        naming a non-quantizable (or absent) layer per-channel must be
        filtered, not explode in quantize_variables."""
        from milnce_tpu.quant.calibrate import calibrate_and_quantize

        report = os.path.join(os.path.dirname(__file__), os.pardir,
                              "NUMERICS.md")
        qvars, calibration = calibrate_and_quantize(
            tiny["model"], tiny["frozen"], numerics_report=report)
        assert calibration["verdict_source"] == report
        assert qvars["quant_scales"]
