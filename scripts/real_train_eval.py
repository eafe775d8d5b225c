"""Real-video train->eval loop on actual encoded bytes (VERDICT r3 #5).

The reference's end-to-end evidence is full HowTo100M training
(/root/reference/train.py:70-225 -> README.md:114-129); no video data
ships in this environment, so this drives the SAME production path —
cv2 decode of real mp4 containers -> HowTo100MSource MIL caption
windows -> sharded train step -> Orbax checkpoint -> the youcook eval
CLI — on a locally-encoded corpus whose video<->text correspondence is
learnable: each class is a colored moving square and every caption
contains the class's vocabulary word.

No FakeDecoder and no synthetic in-memory source anywhere: every
training clip is decoded from mp4 bytes by the production Cv2Decoder
(container seek, fps resample, crop, flip), captions go through the
real JSON track -> MIL candidate-window sampler, and the after-training
retrieval numbers come from the real `milnce_tpu.eval.cli` on held-out
videos.

    python scripts/real_train_eval.py --steps 300 --out REAL_TRAIN.md

Writes the corpus under --root (idempotent), trains, evals the
checkpoint before/after, and appends a markdown report to --out.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# class -> (BGR color, class vocabulary word id offset); colors are far
# apart so mpeg4 quantization at 64x64 cannot blur them together
_COLORS = [(40, 40, 230), (40, 230, 40), (230, 40, 40), (40, 230, 230),
           (230, 40, 230), (230, 230, 40), (40, 140, 230), (230, 230, 230)]


def class_word(c: int) -> str:
    """The caption token that identifies class ``c`` (synthetic_vocab
    naming: 'word<i>'); ids 10.. keep clear of filler words."""
    return f"word{10 + c}"


def _write_video(path: str, cls: int, rng: np.random.RandomState,
                 seconds: float, fps: int, side: int) -> None:
    import cv2

    color = _COLORS[cls % len(_COLORS)]
    sq = side // 3
    x, y = rng.randint(0, side - sq, size=2)
    vx, vy = rng.choice([-2, -1, 1, 2], size=2)
    vw = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), float(fps),
                         (side, side))
    assert vw.isOpened(), path
    for _ in range(int(seconds * fps)):
        frame = rng.randint(0, 30, (side, side, 3)).astype(np.uint8)
        frame[y:y + sq, x:x + sq] = color
        vw.write(frame)
        x += vx
        y += vy
        if not 0 <= x <= side - sq:
            vx = -vx
            x = int(np.clip(x, 0, side - sq))
        if not 0 <= y <= side - sq:
            vy = -vy
            y = int(np.clip(y, 0, side - sq))
    vw.release()


def _caption_track(cls: int, rng: np.random.RandomState,
                   seconds: float) -> dict:
    """HowTo100M-style caption JSON: contiguous ~2.5 s segments, every
    text containing the class word plus random filler (the MIL bag then
    always carries the class signal, like narration does)."""
    starts, ends, texts = [], [], []
    t = 0.0
    while t < seconds - 2.5:
        dur = float(rng.uniform(2.0, 3.0))
        texts.append(f"{class_word(cls)} word{rng.randint(30, 40)} "
                     f"word{rng.randint(40, 50)}")
        starts.append(round(t, 2))
        ends.append(round(min(t + dur, seconds), 2))
        t += dur
    return {"start": starts, "end": ends, "text": texts}


def build_corpus(root: str, classes: int = 8, train_per_class: int = 12,
                 eval_per_class: int = 2, seconds: float = 20.0,
                 fps: int = 8, side: int = 64, seed: int = 0) -> dict:
    """Write the corpus (idempotent via a params marker). Layout:

    root/videos/<id>.mp4 + root/captions/<id>.json + root/train.csv
    root/eval_videos/validation/77/<id>.mp4 + root/eval.csv
    """
    import csv as csv_mod

    params = dict(classes=classes, train_per_class=train_per_class,
                  eval_per_class=eval_per_class, seconds=seconds, fps=fps,
                  side=side, seed=seed, version=1)
    marker = os.path.join(root, "corpus.json")
    out = {"root": root, "train_csv": os.path.join(root, "train.csv"),
           "caption_root": os.path.join(root, "captions"),
           "eval_csv": os.path.join(root, "eval.csv"),
           "eval_root": os.path.join(root, "eval_videos"),
           "n_train": classes * train_per_class,
           "n_eval": classes * eval_per_class}
    if os.path.exists(marker) and json.load(open(marker)) == params:
        return out
    rng = np.random.RandomState(seed)
    os.makedirs(os.path.join(root, "videos"), exist_ok=True)
    os.makedirs(out["caption_root"], exist_ok=True)
    rows = []
    for c in range(classes):
        for j in range(train_per_class):
            vid = f"c{c}v{j}"
            _write_video(os.path.join(root, "videos", vid + ".mp4"), c, rng,
                         seconds, fps, side)
            with open(os.path.join(out["caption_root"], vid + ".json"),
                      "w") as f:
                json.dump(_caption_track(c, rng, seconds), f)
            rows.append(os.path.join("videos", vid + ".mp4"))
    with open(out["train_csv"], "w", newline="") as f:
        w = csv_mod.writer(f)
        w.writerow(["video_path"])
        w.writerows([[r] for r in rows])

    eval_dir = os.path.join(out["eval_root"], "validation", "77")
    os.makedirs(eval_dir, exist_ok=True)
    with open(out["eval_csv"], "w", newline="") as f:
        w = csv_mod.writer(f)
        w.writerow(["end", "start", "task", "text", "video_id"])
        for c in range(classes):
            for j in range(eval_per_class):
                vid = f"ev{c}x{j}"
                _write_video(os.path.join(eval_dir, vid + ".mp4"), c, rng,
                             seconds, fps, side)
                w.writerow([int(seconds) - 2, 2, "77",
                            f"{class_word(c)} word{30 + j}", vid])
    with open(marker, "w") as f:
        json.dump(params, f)
    return out


def build_probe_corpus(root: str, classes: int = 8, per_class: int = 6,
                       seconds: float = 8.0, fps: int = 8, side: int = 64,
                       seed: int = 7) -> dict:
    """HMDB-style labeled corpus for the linear probe (idempotent):
    root/probe_videos/<id>.mp4 + root/probe.csv with the hmdb51.csv
    schema (video_id,label,split1,split2,split3; 1=train 2=test,
    hmdb_loader.py:14-95).  Each split rotates which third of a class's
    videos is held out, so every video is a test sample in exactly one
    split — all three SVMs fit on real disjoint train/test partitions."""
    import csv as csv_mod

    params = dict(classes=classes, per_class=per_class, seconds=seconds,
                  fps=fps, side=side, seed=seed, version=1)
    marker = os.path.join(root, "probe_corpus.json")
    out = {"csv": os.path.join(root, "probe.csv"),
           "video_root": os.path.join(root, "probe_videos"),
           "classes": classes, "n_videos": classes * per_class}
    if os.path.exists(marker) and json.load(open(marker)) == params:
        return out
    rng = np.random.RandomState(seed)
    os.makedirs(out["video_root"], exist_ok=True)
    with open(out["csv"], "w", newline="") as f:
        w = csv_mod.writer(f)
        w.writerow(["video_id", "label", "split1", "split2", "split3"])
        for c in range(classes):
            for j in range(per_class):
                vid = f"c{c}p{j}.mp4"
                _write_video(os.path.join(out["video_root"], vid), c, rng,
                             seconds, fps, side)
                splits = [2 if j % 3 == s else 1 for s in range(3)]
                w.writerow([vid, f"class{c}_test"] + splits)
    with open(marker, "w") as f:
        json.dump(params, f)
    return out


def probe_cli_args(probe: dict, ckpt_dir: str, cfg,
                   num_windows: int = 3) -> list[str]:
    return ["hmdb", "--ckpt", ckpt_dir, "--csv", probe["csv"],
            "--video_root", probe["video_root"], "--platform", "cpu",
            "--num_windows", str(num_windows), "--batch_size", "8",
            "--num_frames", str(cfg.data.num_frames),
            "--video_size", str(cfg.data.video_size),
            "--fps", str(cfg.data.fps),
            "--max_words", str(cfg.data.max_words),
            "--embedding_dim", str(cfg.model.embedding_dim),
            "--inception_blocks", str(cfg.model.inception_blocks),
            "--word_embedding_dim", str(cfg.model.word_embedding_dim),
            "--text_hidden_dim", str(cfg.model.text_hidden_dim),
            "--vocab_size", str(cfg.model.vocab_size)]


def train_config(corpus: dict, root: str, batch: int = 16):
    from milnce_tpu.config import tiny_preset

    cfg = tiny_preset()
    cfg.parallel.platform = "cpu"       # hermetic: a CPU program
    cfg.data.synthetic = False
    cfg.data.train_csv = corpus["train_csv"]
    cfg.data.video_root = corpus["root"]
    cfg.data.caption_root = corpus["caption_root"]
    cfg.data.decoder_backend = "cv2"    # the production in-process decoder
    cfg.data.num_frames = 4
    cfg.data.fps = 4
    cfg.data.video_size = 32
    cfg.data.crop_only = False          # largest-square crop + resize: the
                                        # whole 64px frame lands in the clip
    cfg.data.min_time = 1.0
    cfg.data.max_words = 6
    cfg.data.num_candidates = 3
    cfg.data.num_reader_threads = 8
    cfg.model.embedding_dim = 32
    cfg.model.inception_blocks = 2
    cfg.model.word_embedding_dim = 16
    cfg.model.text_hidden_dim = 32
    cfg.model.vocab_size = 64
    cfg.train.batch_size = batch
    cfg.train.n_display = 10
    cfg.train.checkpoint_keep = 3
    cfg.train.checkpoint_root = os.path.join(root, "ckpt")
    cfg.train.log_root = os.path.join(root, "log")
    cfg.optim.warmup_steps = 20
    cfg.optim.lr = 1e-3
    cfg.optim.epochs = 10_000           # bounded by max_steps
    return cfg


def eval_cli_args(corpus: dict, ckpt_dir: str, cfg) -> list[str]:
    return ["youcook", "--ckpt", ckpt_dir, "--csv", corpus["eval_csv"],
            "--video_root", corpus["eval_root"], "--platform", "cpu",
            "--num_windows", "2", "--batch_size", "8",
            "--num_frames", str(cfg.data.num_frames),
            "--video_size", str(cfg.data.video_size),
            "--fps", str(cfg.data.fps),
            "--max_words", str(cfg.data.max_words),
            "--embedding_dim", str(cfg.model.embedding_dim),
            "--inception_blocks", str(cfg.model.inception_blocks),
            "--word_embedding_dim", str(cfg.model.word_embedding_dim),
            "--text_hidden_dim", str(cfg.model.text_hidden_dim),
            "--vocab_size", str(cfg.model.vocab_size)]


def loss_trajectory(cfg) -> list[float]:
    """Parse 'Training loss: <x>' display lines from the run log
    (RunLogger names the file after the run's checkpoint_dir)."""
    path = os.path.join(cfg.train.log_root,
                        (cfg.train.checkpoint_dir or "run") + ".log")
    losses = []
    if os.path.exists(path):
        for line in open(path):
            if "Training loss:" in line:
                losses.append(float(
                    line.split("Training loss:")[1].split(",")[0]))
    return losses


def run(root: str, steps: int, classes: int = 8, train_per_class: int = 12,
        eval_per_class: int = 2, batch: int = 16, probe: bool = False,
        probe_per_class: int = 6, dtype: str = "float32") -> dict:
    """Build corpus, eval at init, train, eval after; returns the report
    dict.  Importable by tests (scaled down) and by __main__.

    ``probe=True`` additionally runs the HMDB-style linear probe
    (eval/linear_probe.py: mixed_5c features -> LinearSVC(C=100) per
    split -> window-summed top-1, matching eval_hmdb.py:60-104) on a
    separate labeled real-mp4 corpus, before and after training.
    ``dtype`` sets model.dtype — 'bfloat16' reproduces the bench
    operating point's numerics (VERDICT r4 #3)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    from milnce_tpu.eval.cli import main as eval_main
    from milnce_tpu.train.loop import run_training

    corpus = build_corpus(root, classes=classes,
                          train_per_class=train_per_class,
                          eval_per_class=eval_per_class)
    cfg = train_config(corpus, root, batch=batch)
    cfg.model.dtype = dtype
    probe_corpus = (build_probe_corpus(root, classes=classes,
                                       per_class=probe_per_class)
                    if probe else None)

    # "before": one optimizer step in a throwaway run dir — the linear
    # warmup makes the step-0 LR exactly 0, so the checkpointed weights
    # ARE the random init, produced through the full production path.
    cfg.train.checkpoint_dir = "before"
    before_res = run_training(cfg, max_steps=1)
    before_dir = os.path.join(cfg.train.checkpoint_root, "before")
    before = eval_main(eval_cli_args(corpus, before_dir, cfg))
    probe_before = (eval_main(probe_cli_args(probe_corpus, before_dir, cfg))
                    if probe else None)

    cfg.train.checkpoint_dir = "trained"
    result = run_training(cfg, max_steps=steps)
    trained_dir = os.path.join(cfg.train.checkpoint_root, "trained")
    after = eval_main(eval_cli_args(corpus, trained_dir, cfg))
    probe_after = (eval_main(probe_cli_args(probe_corpus, trained_dir, cfg))
                   if probe else None)

    losses = loss_trajectory(cfg)
    return {"corpus": corpus, "steps": result.steps,
            "first_loss": losses[0] if losses else float(before_res.last_loss),
            "final_loss": float(result.last_loss), "losses": losses,
            "before": before, "after": after,
            "chance_r1": 1.0 / corpus["n_eval"], "dtype": dtype,
            "probe_before": probe_before, "probe_after": probe_after,
            "probe_chance": (1.0 / classes) if probe else None,
            "probe_corpus": probe_corpus}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default="/tmp/milnce_real_corpus")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--train_per_class", type=int, default=12)
    ap.add_argument("--eval_per_class", type=int, default=2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--probe", action="store_true",
                    help="also run the HMDB-style linear probe on a "
                         "separate labeled real-mp4 corpus")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--out", default="")
    ap.add_argument("--json_out", default="",
                    help="also dump the raw report dict as JSON (tests)")
    args = ap.parse_args()
    rep = run(args.root, args.steps, classes=args.classes,
              train_per_class=args.train_per_class,
              eval_per_class=args.eval_per_class, batch=args.batch,
              probe=args.probe, dtype=args.dtype)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({k: v for k, v in rep.items()
                       if k not in ("corpus", "probe_corpus")}, f)
    b, a = rep["before"], rep["after"]
    lines = [
        f"# Real-video train->eval (cv2-decoded mp4 corpus, "
        f"dtype={rep['dtype']})", "",
        f"- corpus: {rep['corpus']['n_train']} train / "
        f"{rep['corpus']['n_eval']} eval videos (8 classes, 20 s mpeg4 "
        f"64x64; decoded by Cv2Decoder, no FakeDecoder anywhere)",
        f"- trained {rep['steps']} steps, batch {args.batch}, "
        f"K=3 MIL candidates",
        f"- loss: {rep['first_loss']:.4f} (first display window) -> "
        f"{rep['final_loss']:.4f} (final)",
        f"- loss trajectory (every 10 steps): "
        + ", ".join(f"{v:.3f}" for v in rep["losses"]),
        f"- youcook-CLI retrieval on held-out videos (chance R@1 = "
        f"{rep['chance_r1']:.3f}):",
        f"  - before (init ckpt): R@1 {b['R1']:.3f}, R@5 {b['R5']:.3f}, "
        f"R@10 {b['R10']:.3f}, MR {b['MR']:.1f}",
        f"  - after  (trained):   R@1 {a['R1']:.3f}, R@5 {a['R5']:.3f}, "
        f"R@10 {a['R10']:.3f}, MR {a['MR']:.1f}"]
    if rep["probe_after"] is not None:
        pb, pa = rep["probe_before"], rep["probe_after"]
        lines += [
            f"- HMDB-style linear probe on a separate labeled real-mp4 "
            f"corpus ({rep['probe_corpus']['n_videos']} videos, "
            f"{rep['probe_corpus']['classes']} classes; mixed_5c -> "
            f"LinearSVC(C=100) per split, window-summed top-1; chance = "
            f"{rep['probe_chance']:.3f}):",
            f"  - before (init ckpt): "
            + ", ".join(f"{k} {v:.3f}" for k, v in pb.items()),
            f"  - after  (trained):   "
            + ", ".join(f"{k} {v:.3f}" for k, v in pa.items())]
    lines.append("")
    report = "\n".join(lines)
    print(report)
    if args.out:
        with open(args.out, "a") as f:
            f.write(report + "\n")


if __name__ == "__main__":
    main()
