"""Aux utilities: asset converter CLI, multi-host env detection, the
training divergence guard, and the RunLogger (ISSUE 5 satellite)."""

import json
import threading

import numpy as np
import pytest


class TestRunLogger:
    def test_single_persistent_handle_flushed_per_line(self, tmp_path):
        """The handle is opened ONCE (the old open-per-log() cost a full
        syscall round-trip per display line) and line-buffered: every
        line is on disk the moment log() returns."""
        from milnce_tpu.utils.logging import RunLogger

        logger = RunLogger(str(tmp_path), "run1")
        fh = logger._fh
        logger.log("first")
        assert logger._fh is fh, "log() must not reopen the file"
        # flushed without close: a crash loses at most the current line
        assert "first" in open(logger.path).read()
        logger.log("second")
        assert logger._fh is fh
        lines = open(logger.path).read().splitlines()
        assert len(lines) == 2 and lines[1].endswith("second")
        logger.close()
        assert logger._fh is None
        logger.close()                        # idempotent

    def test_log_event_appends_jsonl_twin(self, tmp_path):
        from milnce_tpu.utils.logging import RunLogger

        logger = RunLogger(str(tmp_path), "run1")
        logger.log_event({"step": 1, "loss": 0.5})
        logger.log_event({"step": 2, "loss": 0.25})
        logger.close()
        records = [json.loads(l) for l in open(logger.events_path)]
        assert records == [{"step": 1, "loss": 0.5},
                           {"step": 2, "loss": 0.25}]

    def test_close_is_terminal_for_both_streams(self, tmp_path):
        # close() must not be resurrectable: a late log()/log_event()
        # from a thread holding a stale reference is a no-op, never a
        # silently reopened handle
        from milnce_tpu.utils.logging import RunLogger

        logger = RunLogger(str(tmp_path), "run1")
        logger.log("before")
        logger.log_event({"step": 1})
        logger.close()
        logger.log("after")
        logger.log_event({"step": 2})
        assert open(logger.path).read().count("\n") == 1
        records = [json.loads(l) for l in open(logger.events_path)]
        assert records == [{"step": 1}]

    def test_disabled_logger_writes_nothing(self, tmp_path):
        from milnce_tpu.utils.logging import RunLogger

        logger = RunLogger(str(tmp_path), "run1", enabled=False)
        logger.log("x")
        logger.log_event({"a": 1})
        logger.close()
        assert logger.path is None and logger.events_path is None

    def test_log_event_racing_close_never_derefs_or_reopens(self, tmp_path):
        """ISSUE 7 regression: log_event's lock-free `_closed` check +
        lazy open-under-lock raced close() (graftlint GL010/GL012) —
        now the nulled handle IS the closed flag, checked under the
        lock.  Writers racing close must never raise, and every line
        that landed is whole valid JSON."""
        import threading

        from milnce_tpu.utils.logging import RunLogger

        logger = RunLogger(str(tmp_path), "run1")
        errors = []

        def writer(tid):
            try:
                for i in range(200):
                    logger.log_event({"t": tid, "i": i})
            except Exception as exc:  # pragma: no cover - asserted below
                errors.append(exc)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        logger.close()                 # races the writers mid-stream
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        records = [json.loads(l) for l in open(logger.events_path)]
        assert all(set(r) == {"t", "i"} for r in records)
        logger.log_event({"late": 1})  # no-op, never a reopened handle
        assert len([json.loads(l) for l in open(logger.events_path)]) \
            == len(records)

    def test_concurrent_writers_interleave_whole_lines(self, tmp_path):
        """Reader threads log decode failures while the loop logs the
        display line — lines must never shear."""
        from milnce_tpu.utils.logging import RunLogger

        logger = RunLogger(str(tmp_path), "run1")
        n, k = 4, 50

        def worker(tid):
            for i in range(k):
                logger.log(f"t{tid}:{i}:{'x' * 64}")

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        logger.close()
        lines = open(logger.path).read().splitlines()
        assert len(lines) == n * k
        assert all(line.endswith("x" * 64) for line in lines)


class TestAssetsCLI:
    def test_word2vec_conversion_roundtrip(self, tmp_path):
        torch = pytest.importorskip("torch")

        from milnce_tpu.models.build import load_word2vec_table
        from milnce_tpu.utils.assets import main

        table = torch.randn(17, 300)
        src = tmp_path / "word2vec.pth"
        dst = tmp_path / "word2vec.npy"
        torch.save(table, src)
        main(["word2vec", str(src), str(dst)])
        loaded = load_word2vec_table(str(dst))
        np.testing.assert_allclose(loaded, table.numpy(), rtol=1e-6)

    def test_word2vec_accepts_embedding_module(self, tmp_path):
        torch = pytest.importorskip("torch")

        from milnce_tpu.utils.assets import convert_word2vec

        emb = torch.nn.Embedding(9, 5)
        src = tmp_path / "emb.pth"
        torch.save(emb, src)
        v, d = convert_word2vec(str(src), str(tmp_path / "emb.npy"))
        assert (v, d) == (9, 5)

    def test_inspect_prints_tensors(self, tmp_path, capsys):
        torch = pytest.importorskip("torch")

        from milnce_tpu.utils.assets import main

        src = tmp_path / "ckpt.pth.tar"
        torch.save({"epoch": 3, "state_dict": {"a.weight": torch.ones(2, 2)}},
                   src)
        main(["inspect", str(src)])
        out = capsys.readouterr().out
        assert "1 entries" in out and "a.weight: (2, 2)" in out


class TestMultihostDetect:
    def test_single_host_is_noop(self, monkeypatch):
        import milnce_tpu.parallel.mesh as mesh_mod
        from milnce_tpu.config import ParallelConfig

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        called = []
        monkeypatch.setattr(mesh_mod.jax.distributed, "initialize",
                            lambda *a, **k: called.append((a, k)))
        mesh_mod.initialize_distributed(ParallelConfig())
        assert called == []

    def test_unset_environment_is_single_host_and_asks_nobody(self,
                                                              monkeypatch):
        """Decided from the environment alone: with the variable unset a
        run is single-host, and the instance metadata (an HTTP lookup
        jax retries with 60 s limits) is never asked."""
        import jax._src.clusters.cloud_tpu_cluster as cluster
        import milnce_tpu.parallel.mesh as mesh_mod
        from milnce_tpu.config import ParallelConfig

        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
        asked, called = [], []
        monkeypatch.setattr(cluster, "get_tpu_env_value",
                            lambda *a, **k: asked.append(a) or "a,b")
        monkeypatch.setattr(mesh_mod.jax.distributed, "initialize",
                            lambda *a, **k: called.append((a, k)))
        mesh_mod.initialize_distributed(ParallelConfig())
        assert asked == [] and called == []

    def test_multihost_tpu_auto_initializes(self, monkeypatch):
        import milnce_tpu.parallel.mesh as mesh_mod
        from milnce_tpu.config import ParallelConfig

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1w-0,t1w-1,t1w-2")
        called = []
        monkeypatch.setattr(mesh_mod.jax.distributed, "initialize",
                            lambda *a, **k: called.append((a, k)))
        mesh_mod.initialize_distributed(ParallelConfig())
        assert called == [((), {})]     # bare call: TPU metadata autodetect

    def test_explicit_coordinator_wins(self, monkeypatch):
        import milnce_tpu.parallel.mesh as mesh_mod
        from milnce_tpu.config import ParallelConfig

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1w-0,t1w-1")
        called = []
        monkeypatch.setattr(mesh_mod.jax.distributed, "initialize",
                            lambda *a, **k: called.append(k))
        cfg = ParallelConfig(coordinator_address="10.0.0.1:8476",
                             num_processes=2, process_id=1)
        mesh_mod.initialize_distributed(cfg)
        assert called[0]["coordinator_address"] == "10.0.0.1:8476"
        assert called[0]["num_processes"] == 2

    def test_platform_pin_applies_jax_config(self, monkeypatch):
        """--parallel.platform pins the backend via jax.config; ''
        leaves it untouched."""
        import milnce_tpu.parallel.mesh as mesh_mod
        from milnce_tpu.config import ParallelConfig, parse_cli

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        updates = []
        monkeypatch.setattr(mesh_mod.jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        mesh_mod.initialize_distributed(ParallelConfig())
        assert updates == []                        # default: no pin
        mesh_mod.initialize_distributed(ParallelConfig(platform="cpu"))
        assert updates == [("jax_platforms", "cpu")]
        # threaded through the CLI front-end
        cfg = parse_cli(["--parallel.platform", "cpu"])
        assert cfg.parallel.platform == "cpu"

    def test_platform_pin_skips_multihost_autojoin(self, monkeypatch):
        """A CPU-pinned hermetic run on a multi-host TPU slice must NOT
        auto-join the pod's distributed cluster (it would block at the
        coordinator barrier waiting for never-launched workers)."""
        import milnce_tpu.parallel.mesh as mesh_mod
        from milnce_tpu.config import ParallelConfig

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1w-0,t1w-1,t1w-2")
        monkeypatch.setattr(mesh_mod.jax.config, "update", lambda k, v: None)
        called = []
        monkeypatch.setattr(mesh_mod.jax.distributed, "initialize",
                            lambda *a, **k: called.append((a, k)))
        mesh_mod.initialize_distributed(ParallelConfig(platform="cpu"))
        assert called == []
        # explicit coordinator still wins even with a pin
        mesh_mod.initialize_distributed(ParallelConfig(
            platform="cpu", coordinator_address="10.0.0.1:8476",
            num_processes=3, process_id=0))
        assert len(called) == 1


@pytest.mark.slow
class TestNaNGuard:
    def test_halts_and_checkpoints_on_nan(self, tmp_path):
        """A synthetic source whose batches drive the loss to NaN must
        halt with FloatingPointError at the first display fetch."""
        from milnce_tpu.config import tiny_preset
        from milnce_tpu.train.loop import run_training

        cfg = tiny_preset()
        cfg.train.checkpoint_root = str(tmp_path / "ckpt")
        cfg.train.log_root = str(tmp_path / "log")
        cfg.train.batch_size = 8
        cfg.data.synthetic_num_samples = 16
        cfg.data.num_reader_threads = 1
        cfg.train.n_display = 1
        cfg.optim.lr = 1e18                # diverge within a couple of steps
        cfg.optim.warmup_steps = 0
        with pytest.raises(FloatingPointError, match="non-finite"):
            run_training(cfg, max_steps=8)
        # post-mortem snapshot exists, OUTSIDE the resume rotation
        pm = tmp_path / "ckpt" / "run" / "nan_postmortem"
        assert pm.is_dir() and any(pm.iterdir())

    def test_guard_disabled_keeps_running(self, tmp_path):
        from milnce_tpu.config import tiny_preset
        from milnce_tpu.train.loop import run_training

        cfg = tiny_preset()
        cfg.train.checkpoint_root = str(tmp_path / "ckpt")
        cfg.train.log_root = str(tmp_path / "log")
        cfg.train.batch_size = 8
        cfg.data.synthetic_num_samples = 16
        cfg.data.num_reader_threads = 1
        cfg.train.n_display = 1
        cfg.train.halt_on_nan = False
        cfg.optim.lr = 1e18
        cfg.optim.warmup_steps = 0
        result = run_training(cfg, max_steps=2)
        assert result.steps == 2


class TestFlagReducer:
    def test_overlap_mode_pipelines_one_boundary_behind(self):
        """overlap=True returns the PREVIOUS boundary's verdict (never
        blocks on the collective it just enqueued): a flag raised at
        boundary k is visible at k+1, uniformly across the mesh
        (ADVICE r4, parallel/mesh.py)."""
        import jax

        from milnce_tpu.config import ParallelConfig
        from milnce_tpu.parallel.mesh import build_mesh, make_flag_reducer

        mesh = build_mesh(ParallelConfig(), jax.devices())

        blocking = make_flag_reducer(mesh)
        assert blocking(False) is False
        assert blocking(True) is True            # same-boundary verdict

        lagged = make_flag_reducer(mesh, overlap=True)
        assert lagged(False) is False            # nothing pending yet
        assert lagged(True) is False             # enqueued, not yet read
        assert lagged(False) is True             # previous boundary's flag
        assert lagged(False) is False
