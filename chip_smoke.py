"""Chip smoke: the trainer and the retrieval engine, through the entry
points a user calls, at the full width of the model — on a TPU or not
at all.

    python chip_smoke.py             # one chip: phases 1-4
    python chip_smoke.py --chips 4   # four chips: the data-parallel step
                                     # against the one-device step, only

One process, which takes the chip itself and starts no child.  It
refuses to start unless ``jax.devices()[0].platform == "tpu"``; any phase
that fails raises, so the script exits nonzero and prints no result.
The LAST line of standard output is the result and nothing more:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip):

1. trainer — ``milnce_tpu.train.cli.main`` at the ``full`` preset's model
   (S3D-G, 9 Inception blocks, gating, 512/66250/300/2048), 32f@224,
   K=5, 20 words, bfloat16, MIL-NCE, synthetic data from a seed; a
   checkpoint saved, then restored by a second call with
   ``--train.resume true``.  Only the batch and the step count are cut.
2. alignment loss — two steps of the same trainer in float32 with
   ``--loss.name sdtw_3 --loss.sdtw_backend pallas`` (the compiled step
   must hold a ``tpu_custom_call``), then ``softdtw_pallas`` against
   ``softdtw_scan``, value and gradient.
3. chunked MIL-NCE — ``milnce_loss_chunked(backend='pallas')`` against
   the dense loss, value and gradients, at a shape ``prefers_pallas``
   selects.
4. retrieval engine — ``milnce-export`` of phase 1's checkpoint,
   ``milnce-serve``'s ``build_server`` (ladder precompiled), a few
   hundred synthetic clips embedded into the device-resident index, the
   HTTP front in a thread of this process, text queries and raw clips
   over the socket, every answer checked against a numpy top-k.

The phases are functions of a :class:`Sizes`, so that
tests/test_chip_smoke.py rehearses them at the ``tiny`` preset on the CPU
(Pallas in interpret mode; no ``tpu_custom_call`` is asked for there).
What is printed here are smoke observations, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


@dataclasses.dataclass(frozen=True)
class Sizes:
    preset: str                 # config preset whose MODEL runs untouched
    model_overrides: tuple      # extra --model.* flags (depth cut: tiny only)
    frames: int
    size: int
    candidates: int
    words: int
    batch: int                  # phase 1 (bfloat16) global batch
    f32_batch: int              # phase 2 and --chips 4 (float32) global batch
    train_steps: int            # phase 1 steps before the resume
    softdtw_shapes: tuple       # (B, N, M) for phase 2's kernel check
    milnce_shape: tuple         # (b, K, D, chunk) for phase 3
    corpus_clips: int
    serve_buckets: tuple        # (min_bucket, max_batch)
    socket_clips: int           # raw clips sent over the socket


# the full preset's model, the paper's input recipe; batch and steps are
# what one v5e chip (16 GB) takes in a few minutes: the TPU compiler puts
# the bfloat16 step at 7.6 GB for batch 16 (13.2 GB for 32) and the
# float32 sdtw_3 step at 12.6 GB for batch 16, so float32 runs at 8
FULL_SIZES = Sizes(
    preset="full", model_overrides=(), frames=32, size=224, candidates=5,
    words=20, batch=16, f32_batch=8, train_steps=4,
    softdtw_shapes=((128, 17, 15), (1024, 32, 32)),
    milnce_shape=(128, 5, 512, 8), corpus_clips=256, serve_buckets=(8, 16),
    socket_clips=2)

# CPU rehearsal: same phases, same code, toy sizes
TINY_SIZES = Sizes(
    preset="tiny", model_overrides=("--model.inception_blocks", "1"),
    frames=4, size=32, candidates=2, words=6, batch=8, f32_batch=8,
    train_steps=4, softdtw_shapes=((16, 6, 5),),
    milnce_shape=(8, 2, 128, 8), corpus_clips=24, serve_buckets=(8, 8),
    socket_clips=2)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# --------------------------------------------------------------------------
# the trainer, observed
# --------------------------------------------------------------------------

class _StepRecorder:
    """Watches the step the trainer builds — it changes nothing.  While
    installed, ``train.loop.make_train_step`` returns the real jitted
    step behind a wrapper that notes, at the first call, the arguments'
    shapes and shardings, a host copy of the initial parameters and the
    devices holding the batch: what a check of the COMPILED step needs
    after ``cli.main`` has returned."""

    def __init__(self, keep_initial_params: bool = False):
        self.keep_initial_params = keep_initial_params
        self.jitted = None
        self.abstract_args = None
        self.initial_params = None
        self.batch_device_ids = None

    def __enter__(self):
        from milnce_tpu.train import loop

        self._loop, self._real = loop, loop.make_train_step

        def make(*args, **kwargs):
            import jax

            self.jitted = self._real(*args, **kwargs)

            def step(state, video, text, start):
                if self.abstract_args is None:
                    self.abstract_args = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding),
                        (state, video, text, start))
                    self.batch_device_ids = sorted(
                        s.device.id for s in video.addressable_shards)
                    if self.keep_initial_params:
                        self.initial_params = jax.device_get(state.params)
                return self.jitted(state, video, text, start)

            return step

        loop.make_train_step = make
        return self

    def __exit__(self, *exc):
        self._loop.make_train_step = self._real

    def compiled_text(self) -> str:
        """Text of the step as compiled for the devices it ran on (the
        same program the run compiled: a persistent-cache hit)."""
        return self.jitted.lower(*self.abstract_args).compile().as_text()


def _train_argv(sizes: Sizes, platform: str, work: str, name: str, *,
                batch: int, dtype: str, extra=()) -> list:
    return ["--preset", sizes.preset, *sizes.model_overrides,
            "--model.dtype", dtype,
            "--data.synthetic", "true",
            "--data.synthetic_num_samples", str(batch * 64),
            "--data.num_frames", str(sizes.frames),
            "--data.video_size", str(sizes.size),
            "--data.num_candidates", str(sizes.candidates),
            "--data.max_words", str(sizes.words),
            "--train.batch_size", str(batch),
            "--train.n_display", "1",
            "--train.seed", "1",
            "--parallel.platform", platform,
            "--train.checkpoint_root", os.path.join(work, "ckpt"),
            "--train.checkpoint_dir", name,
            "--train.log_root", os.path.join(work, "log", name),
            *extra]


def _run_events(work: str, name: str) -> list:
    path = os.path.join(work, "log", name, "RUN_EVENTS.jsonl")
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _report_steps(events: list, label: str, expect_steps: int,
                  first_step: int = 1) -> list:
    """Per step: host time from dispatch to the materialised loss (the
    ``step`` span plus the ``sync`` span that fetches it — the loop's
    ``block_until_ready``), the loss, the guard's skipped count.  The
    first step of a call carries the compile; it is printed apart."""
    dispatch = {e["step"]: e["dur_ms"] for e in events
                if e.get("kind") == "span" and e.get("name") == "step"}
    displays = [e for e in events if e.get("name") == "display"]
    syncs = [e["dur_ms"] for e in events
             if e.get("kind") == "span" and e.get("name") == "sync"
             and e.get("cause") == "display"]
    assert len(displays) == expect_steps == len(syncs), (
        f"{label}: {len(displays)} displayed steps, expected {expect_steps}")
    assert displays[0]["step"] == first_step, (
        f"{label}: first step is {displays[0]['step']}, expected "
        f"{first_step}")
    losses = []
    for i, (d, sync_ms) in enumerate(zip(displays, syncs)):
        host_ms = dispatch[i + 1] + sync_ms
        what = "compile + first step" if i == 0 else "step"
        say(f"{label}: {what} {d['step']}: host {host_ms:.1f} ms "
            f"(dispatch {dispatch[i + 1]:.1f} + sync {sync_ms:.1f}), "
            f"loss {d['loss']:.6f}, skipped {d['skipped_total']}")
        assert np.isfinite(d["loss"]), f"{label}: loss {d['loss']}"
        assert d["skipped_total"] == 0, (
            f"{label}: the finite-update guard skipped an update")
        losses.append(d["loss"])
    return losses


def _peak_bytes(devices, require: bool) -> list:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            if require:
                raise RuntimeError(
                    f"device {d.id} ({d.device_kind}) reports no "
                    "memory_stats()['peak_bytes_in_use']")
            peaks.append(None)
        else:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def phase_trainer(sizes: Sizes, platform: str, work: str) -> str:
    """Phase 1.  Returns the checkpoint directory for phase 4."""
    import jax

    from milnce_tpu.train import cli

    say(f"phase 1: trainer, preset={sizes.preset} "
        f"{sizes.frames}f@{sizes.size} K={sizes.candidates} "
        f"words={sizes.words} bfloat16 milnce, batch {sizes.batch}, "
        f"{sizes.train_steps} steps + 2 after the resume (chosen sizes)")
    argv = _train_argv(sizes, platform, work, "p1", batch=sizes.batch,
                       dtype="bfloat16")
    cli.main(argv + ["--train.max_steps", str(sizes.train_steps)])
    _report_steps(_run_events(work, "p1"), "trainer", sizes.train_steps)
    ckpt_dir = os.path.join(work, "ckpt", "p1")
    assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir), (
        "max_steps stop saved no checkpoint")
    # the normal resume path: same command line plus --train.resume true
    os.rename(os.path.join(work, "log", "p1"),
              os.path.join(work, "log", "p1_first"))
    cli.main(argv + ["--train.resume", "true", "--train.max_steps", "2"])
    events = _run_events(work, "p1")
    restores = [e for e in events if e.get("name") in ("ckpt.restore",
                                                       "elastic.resume")]
    assert restores and "error" not in restores[0], (
        "the resumed run restored no checkpoint")
    _report_steps(events, "trainer (resumed)", 2,
                  first_step=sizes.train_steps + 1)
    peaks = _peak_bytes(jax.local_devices()[:1], require=platform == "tpu")
    say(f"phase 1: peak_bytes_in_use {peaks[0]}")
    return ckpt_dir


# --------------------------------------------------------------------------
# phase 2: the alignment loss on the compiled kernel
# --------------------------------------------------------------------------

def _assert_custom_call(text: str, what: str, platform: str) -> None:
    if platform == "tpu":
        assert "tpu_custom_call" in text, (
            f"{what}: no tpu_custom_call in the compiled text — the "
            "Pallas kernel did not compile for the chip")
        say(f"{what}: tpu_custom_call present in the compiled text")


def phase_alignment(sizes: Sizes, platform: str, work: str) -> None:
    import jax
    import jax.numpy as jnp

    from milnce_tpu.ops.softdtw import softdtw_scan
    from milnce_tpu.ops.softdtw_pallas import softdtw_pallas
    from milnce_tpu.train import cli

    say(f"phase 2: trainer float32 sdtw_3 / sdtw_backend=pallas, "
        f"batch {sizes.f32_batch}, 2 steps")
    with _StepRecorder() as seen:
        cli.main(_train_argv(
            sizes, platform, work, "p2", batch=sizes.f32_batch,
            dtype="float32",
            extra=["--loss.name", "sdtw_3", "--loss.sdtw_backend", "pallas",
                   "--train.max_steps", "2"]))
    _report_steps(_run_events(work, "p2"), "trainer sdtw_3", 2)
    _assert_custom_call(seen.compiled_text(), "sdtw_3 train step", platform)

    tol = 1e-3      # ops/softdtw_profile.py's cross-check tolerance
    for bsz, n, m in sizes.softdtw_shapes:
        rng = np.random.RandomState(0)
        x = rng.randn(bsz, n, 2).astype(np.float32)
        y = rng.randn(bsz, m, 2).astype(np.float32)
        D = jnp.asarray(((x[:, :, None, :] - y[:, None, :, :]) ** 2).mean(-1))
        pallas = jax.jit(jax.value_and_grad(
            lambda d: jnp.sum(softdtw_pallas(d, 1.0))))
        scan = jax.jit(jax.value_and_grad(
            lambda d: jnp.sum(softdtw_scan(d, 1.0))))
        _assert_custom_call(pallas.lower(D).compile().as_text(),
                            f"softdtw_pallas {(bsz, n, m)}", platform)
        (v_p, g_p), (v_s, g_s) = pallas(D), scan(D)
        dv = float(abs(v_p - v_s))
        dg = float(jnp.max(jnp.abs(g_p - g_s)))
        say(f"softdtw {(bsz, n, m)}: |dvalue| {dv:.3e} (value "
            f"{float(v_s):.4f}), max|dgrad| {dg:.3e}")
        assert np.allclose(v_p, v_s, atol=tol * bsz, rtol=tol), "value"
        assert np.allclose(g_p, g_s, atol=tol, rtol=tol), "gradient"


# --------------------------------------------------------------------------
# phase 3: the chunked MIL-NCE stream on the compiled kernel
# --------------------------------------------------------------------------

def phase_milnce_kernel(sizes: Sizes, platform: str) -> None:
    import jax
    import jax.numpy as jnp

    from milnce_tpu.losses.milnce import milnce_loss
    from milnce_tpu.losses.milnce_chunked import milnce_loss_chunked
    from milnce_tpu.ops.milnce_pallas import prefers_pallas

    b, k, d, chunk = sizes.milnce_shape
    assert prefers_pallas(b, b, k, d, chunk), (
        f"backend='auto' would not select the kernel at {sizes.milnce_shape}")
    say(f"phase 3: milnce_loss_chunked(backend='pallas') vs dense at "
        f"b=Bg={b}, K={k}, D={d}, chunk={chunk}")
    rng = np.random.RandomState(3)
    # unit-variance logits: the comparison is about the kernel, not about
    # exp() of dot products of magnitude sqrt(D)
    v = jnp.asarray(rng.randn(b, d).astype(np.float32) / d ** 0.25)
    t = jnp.asarray(rng.randn(b * k, d).astype(np.float32) / d ** 0.25)

    kernel = jax.jit(jax.value_and_grad(
        lambda a, c: milnce_loss_chunked(a, c, chunk=chunk,
                                         backend="pallas"), argnums=(0, 1)))
    dense = jax.jit(jax.value_and_grad(
        lambda a, c: milnce_loss(a, c), argnums=(0, 1)))
    _assert_custom_call(kernel.lower(v, t).compile().as_text(),
                        "chunked MIL-NCE", platform)
    # the reference in true float32 (the chip's default f32 matmul is a
    # bfloat16 pass, which is the reference's fault, not the kernel's)
    with jax.default_matmul_precision("highest"):
        val_d, grads_d = dense(v, t)
    val_k, grads_k = kernel(v, t)
    dv = float(abs(val_k - val_d))
    say(f"chunked MIL-NCE: value {float(val_k):.6f} vs dense "
        f"{float(val_d):.6f} (|d| {dv:.3e})")
    assert dv <= 5e-3 * abs(float(val_d)) + 1e-5, "value"
    for name, gk, gd in zip(("dL/dvideo", "dL/dtext"), grads_k, grads_d):
        scale = float(jnp.max(jnp.abs(gd)))
        diff = float(jnp.max(jnp.abs(gk - gd)))
        say(f"chunked MIL-NCE: {name} max|d| {diff:.3e} "
            f"(max|grad| {scale:.3e})")
        assert diff <= 2e-2 * scale + 1e-7, name


# --------------------------------------------------------------------------
# phase 4: the retrieval engine
# --------------------------------------------------------------------------

def _post(port: int, path: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=600) as resp:
        return json.loads(resp.read())


def _check_topk(answer: dict, query_emb: np.ndarray, corpus: np.ndarray,
                k: int, label: str) -> None:
    """The served ranking against a plain numpy top-k over the same
    embeddings: the scores at the returned indices are the numpy scores,
    and no index left out scores higher than one returned (ties inside
    float tolerance may order either way)."""
    scores = corpus @ query_emb
    idx = np.asarray(answer["indices"])
    got = np.asarray(answer["scores"], np.float32)
    assert idx.shape == (k,) and len(set(idx.tolist())) == k, label
    tol = 1e-3 * (1.0 + float(np.max(np.abs(scores))))
    np.testing.assert_allclose(got, scores[idx], atol=tol, err_msg=label)
    kth = np.sort(scores)[-k]
    assert float(np.min(scores[idx])) >= kth - tol, (
        f"{label}: a returned index is not in the numpy top-{k}")


def phase_retrieval(sizes: Sizes, platform: str, work: str,
                    ckpt_dir: str) -> None:
    from milnce_tpu.config import parse_cli
    from milnce_tpu.serving import export, service

    say(f"phase 4: retrieval engine, preset={sizes.preset} "
        f"{sizes.frames}f@{sizes.size}, {sizes.corpus_clips} clips, "
        f"buckets {sizes.serve_buckets}")
    export_dir = os.path.join(work, "export")
    t0 = time.perf_counter()
    export.main(["--checkpoint_dir", ckpt_dir, "--out", export_dir,
                 "--preset", sizes.preset, *sizes.model_overrides,
                 "--model.dtype", "bfloat16",
                 "--data.num_frames", str(sizes.frames),
                 "--data.video_size", str(sizes.size),
                 "--data.max_words", str(sizes.words)])
    say(f"export: {time.perf_counter() - t0:.1f} s")

    topk = 5
    min_bucket, max_batch = sizes.serve_buckets
    cfg = parse_cli(
        ["--preset", sizes.preset, *sizes.model_overrides,
         "--parallel.platform", platform,
         "--serve.export_dir", export_dir, "--serve.port", "0",
         "--serve.max_batch", str(max_batch),
         "--serve.min_bucket", str(min_bucket),
         "--serve.topk", str(topk), "--serve.live_index", "true"],
        description="chip_smoke serving front")
    t0 = time.perf_counter()
    server, svc, index, engine = service.build_server(cfg)
    say(f"build_server (engine ladder {engine.buckets} precompiled, empty "
        f"live index): {time.perf_counter() - t0:.1f} s")
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    port = server.server_address[1]
    try:
        rng = np.random.RandomState(4)
        clip_shape = (sizes.frames, sizes.size, sizes.size, 3)
        corpus = []
        t0 = time.perf_counter()
        for lo in range(0, sizes.corpus_clips, max_batch):
            n = min(max_batch, sizes.corpus_clips - lo)
            clips = rng.randint(0, 256, (n,) + clip_shape, dtype=np.uint8)
            corpus.append(np.asarray(engine.embed_video(clips), np.float32))
        corpus = np.concatenate(corpus)
        assert corpus.shape == (sizes.corpus_clips, engine.embed_dim)
        assert np.isfinite(corpus).all(), "non-finite clip embedding"
        say(f"embedded {sizes.corpus_clips} clips in "
            f"{time.perf_counter() - t0:.1f} s")
        out = svc.index_add(embeddings=corpus, wait=True)
        say(f"index: {out}")

        # video over the socket: raw clips through the video tower
        clips = rng.randint(0, 256, (sizes.socket_clips,) + clip_shape,
                            dtype=np.uint8)
        t0 = time.perf_counter()
        out = _post(port, "/v1/index/add",
                    {"clips": clips.tolist(), "wait": True})
        say(f"/v1/index/add {sizes.socket_clips} raw clips: {out} in "
            f"{time.perf_counter() - t0:.1f} s")
        corpus = np.concatenate(
            [corpus, np.asarray(engine.embed_video(clips), np.float32)])

        for q in range(6):
            ids = rng.randint(1, cfg.model.vocab_size,
                              (1, sizes.words)).tolist()
            t0 = time.perf_counter()
            answer = _post(port, "/v1/query", {"token_ids": ids, "k": topk})
            ms = (time.perf_counter() - t0) * 1e3
            emb = np.asarray(_post(port, "/v1/embed_text",
                                   {"token_ids": ids})["embeddings"][0],
                             np.float32)
            assert np.isfinite(emb).all()
            _check_topk(answer["results"][0], emb, corpus, topk,
                        f"text query {q}")
            say(f"text query {q}: {ms:.1f} ms over the socket, top-{topk} "
                f"{answer['results'][0]['indices']} agrees with numpy "
                f"(generation {answer.get('index_generation')})")
        health = _get(port, "/healthz")
        recompiles = health["engine"]["recompiles"]
        say(f"/healthz: engine recompiles {recompiles}, index size "
            f"{health['index']['size']}")
        assert recompiles == 0, "the ladder recompiled on the query path"
        assert health["index"]["size"] == len(corpus)
    finally:
        server.shutdown()
        thread.join(timeout=30)
        service.close_server(cfg, server, svc, index, engine)


# --------------------------------------------------------------------------
# --chips 4: the data-parallel step against the one-device step
# --------------------------------------------------------------------------

def phase_data_parallel(sizes: Sizes, platform: str, work: str,
                        n_devices: int = 4) -> None:
    """The trainer's step on an ``n_devices`` data mesh (mesh-wide
    negatives by all_gather, gradient psum, BN statistics by pmean) and
    on a one-device mesh of this same process: same seed, same global
    batch, sync BN so both see the same statistics, SGD so that a wrong
    gradient scale cannot hide behind Adam.  Two steps: the schedule's
    first learning rate is 0, so the second step is the one update, made
    from the same parameters on both meshes — compared directly, before
    a tiny float32 difference can grow through further steps."""
    import jax

    steps, batch = 2, sizes.f32_batch
    # Both meshes in TRUE float32.  At the chip's default precision a
    # float32 conv is a bfloat16 pass, and the two meshes tile a batch of
    # 2 and of 8 differently: the FIRST loss, from identical parameters
    # and data, already differed by 0.76% between four chips and one —
    # rounding, not a fault, but too coarse to show a fault against.
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        _compare_meshes(sizes, platform, work, n_devices, steps, batch)
    finally:
        jax.config.update("jax_default_matmul_precision", was)


def _compare_meshes(sizes: Sizes, platform: str, work: str, n_devices: int,
                    steps: int, batch: int) -> None:
    import jax

    from milnce_tpu.config import parse_cli
    from milnce_tpu.train.loop import run_training

    say(f"--chips {n_devices}: trainer float32 milnce, sync BN, SGD, "
        f"global batch {batch}, {steps} steps, on {n_devices} devices and "
        "on 1")
    runs = {}
    for n in (n_devices, 1):
        name = f"dp{n}"
        argv = _train_argv(
            sizes, platform, work, name, batch=batch, dtype="float32",
            extra=["--parallel.num_devices", str(n),
                   "--model.sync_batchnorm", "true",
                   "--optim.name", "sgd", "--optim.lr", "0.05",
                   "--optim.warmup_steps", "1",
                   "--train.max_steps", str(steps)])
        with _StepRecorder(keep_initial_params=True) as seen:
            result = run_training(parse_cli(argv))
        losses = _report_steps(_run_events(work, name),
                               f"{n}-device step", steps)
        text = seen.compiled_text()
        say(f"{n}-device step: batch shards on devices "
            f"{seen.batch_device_ids}")
        if n > 1:
            assert len(seen.batch_device_ids) == n, (
                "not every device holds a shard of the batch")
            for op in ("all-gather", "all-reduce"):
                assert op in text, f"no {op} in the {n}-device step"
            say(f"{n}-device step: all-gather and all-reduce present in "
                "the compiled text")
            peaks = _peak_bytes(jax.devices()[:n], require=platform == "tpu")
            say(f"{n}-device step: peak_bytes_in_use per device {peaks}")
            assert all(p is None or p > 0 for p in peaks)
        runs[n] = (losses, jax.device_get(result.state.params),
                   seen.initial_params)

    (l_n, p_n, p0), (l_1, p_1, _) = runs[n_devices], runs[1]
    say(f"losses {n_devices}-device {l_n} vs 1-device {l_1}")
    np.testing.assert_allclose(l_n, l_1, rtol=2e-3, atol=1e-4)
    leaves = jax.tree_util.tree_leaves_with_path
    worst_l2, worst_abs, moved = (0.0, ""), (0.0, ""), 0.0
    for (path, a), (_, b), (_, z) in zip(leaves(p_n), leaves(p_1),
                                         leaves(p0)):
        name = jax.tree_util.keystr(path)
        a, b, z = (np.asarray(x, np.float64) for x in (a, b, z))
        update, diff = b - z, a - b
        if not update.any():            # a frozen leaf stays put on both
            assert not diff.any(), f"{name}: moved on one mesh only"
            continue
        # stated tolerance, per leaf: the difference of the two updates
        # within 10% of the update in L2 (a gradient n times too large or
        # too small misses by 75% at the least), and no single element
        # off by more than a quarter of the leaf's largest update (ReLU
        # and max-pool ties flipped by float32 summation order are sparse)
        rel_l2 = float(np.linalg.norm(diff) / np.linalg.norm(update))
        rel_abs = float(np.max(np.abs(diff)) / np.max(np.abs(update)))
        assert rel_l2 <= 0.10 and rel_abs <= 0.25, (
            f"{name}: updates differ by {100 * rel_l2:.2f}% in L2, "
            f"{100 * rel_abs:.2f}% of the largest update at worst")
        moved = max(moved, float(np.max(np.abs(update))))
        worst_l2 = max(worst_l2, (rel_l2, name))
        worst_abs = max(worst_abs, (rel_abs, name))
    assert moved > 1e-4, f"SGD barely moved the parameters ({moved})"
    say(f"updated parameters equal leaf for leaf: largest update "
        f"{moved:.3e}; worst relative L2 difference of a leaf's update "
        f"{100 * worst_l2[0]:.3f}% ({worst_l2[1]}), worst single element "
        f"{100 * worst_abs[0]:.3f}% of its leaf's largest update "
        f"({worst_abs[1]})")


# --------------------------------------------------------------------------

def run_phases(sizes: Sizes, platform: str, work: str) -> None:
    """Phases 1-4 on one device, in order; the first failure raises."""
    ckpt_dir = phase_trainer(sizes, platform, work)
    phase_alignment(sizes, platform, work)
    phase_milnce_kernel(sizes, platform)
    phase_retrieval(sizes, platform, work, ckpt_dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the data-parallel step against the "
                         "one-device step, and no other phase")
    args = ap.parse_args(argv)

    import jax

    from milnce_tpu.native.build import native_available
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found platform={dev.platform!r}) — "
              "refusing to start", file=sys.stderr)
        return 2
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2
    import jaxlib

    say(f"device_kind={dev.device_kind!r} devices={len(jax.devices())} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"compile cache at {cache_dir}")
    say(f"native reader built: {native_available()}")
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        if args.chips == 4:
            phase_data_parallel(FULL_SIZES, "tpu", work, n_devices=4)
        else:
            run_phases(FULL_SIZES, "tpu", work)
    say(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
