"""Params-only frozen export: training checkpoint -> inference artifact.

A training checkpoint (Orbax, train/checkpoint.py) carries the full
``TrainState`` — params, BatchNorm stats, AND the optimizer moments,
which for Adam are 2x the params and pure dead weight at serve time.
This module writes the inference subset in a deliberately boring
format: one ``arrays.npz`` (flattened ``params`` + ``batch_stats``
leaves, '/'-joined tree paths as keys) plus one ``metadata.json``
(model config, tokenizer contract, per-clip video shape) — loadable on
any host with numpy, no Orbax, no original mesh, no model code at read
time.

A float leaf is stored in its own type (float64 never ships: it is
stored float32); a training checkpoint's leaves are float32, so its
export is, and casting to bf16 stays a LOAD-time decision
(``InferenceEngine.from_export(dtype='bfloat16')``): one artifact serves
both precision modes.  A tree that is bfloat16 already (a language-model
tower of billions of parameters) is written as it is, bit for bit —
``.npz`` has no bfloat16, so those leaves lie there as their 16 bits
(uint16) and ``array_dtypes`` says what they are.

CLI (console script ``milnce-export`` /
``python -m milnce_tpu.serving.export``)::

    milnce-export --checkpoint_dir checkpoint/run1 --out export/run1 \\
        --preset small [--epoch 7] [--model.embedding_dim 512 ...]

The model/data flags mirror the trainer CLI: the checkpoint stores only
arrays, so the exporter must be told the same model config the run was
trained with (preset + overrides), and bakes it into the artifact —
the serving host never guesses shapes again.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Optional

import numpy as np

ARRAYS_FILE = "arrays.npz"
METADATA_FILE = "metadata.json"
FORMAT_VERSION = 1

# Quantized edge-tier artifact (milnce_tpu/quant/): SAME two files, but
# quantized params ship int8 with their f32 scales under the
# 'quant_scales/' key prefix, the array_dtypes manifest records 'int8'
# entries, and metadata carries a 'quant' block (scheme + calibration
# summary).  A separate format version so the v1 loader rejects it
# LOUDLY instead of serving int8 bits as weights.
QUANT_FORMAT_VERSION = 2
SCALES_PREFIX = "quant_scales"

# Live-index corpus snapshot (serving/live_index.py): the SAME boring
# two-file shape as the params export — one npz (the corpus under the
# 'emb' key, the exact array ``--serve.corpus_npz`` accepts) plus one
# versioned metadata json — so an ingesting service can checkpoint its
# grown corpus and a restore (or a cold boot off the npz alone) is
# bit-exact.
INDEX_ARRAYS_FILE = "corpus.npz"
INDEX_METADATA_FILE = "index_meta.json"
INDEX_FORMAT_VERSION = 1


def export_corpus_snapshot(out_dir: str, embeddings: np.ndarray, *,
                           generation: int, k: int,
                           source: str = "") -> str:
    """Write a live-index corpus snapshot; returns ``out_dir``.

    ``embeddings`` is the LIVE generation's (N, D) float32 host corpus
    (pending ingest rows are the caller's business — flush first)."""
    emb = np.ascontiguousarray(embeddings, dtype=np.float32)
    if emb.ndim != 2:
        raise ValueError(f"expected (N, D) embeddings, got {emb.shape}")
    os.makedirs(out_dir, exist_ok=True)
    meta = {
        "format_version": INDEX_FORMAT_VERSION,
        "generator": "milnce_tpu/serving/export.py (corpus snapshot)",
        "generation": int(generation),
        "k": int(k),
        "size": int(emb.shape[0]),
        "dim": int(emb.shape[1]),
        "source": source,
    }
    # tmp-write + atomic rename, corpus first: an ingesting service
    # snapshots into the SAME directory every shutdown, so an in-place
    # write killed mid-stream would destroy the previous good snapshot
    # (the exact crash-window class train/checkpoint.py defends
    # against).  Worst case after a crash between the two renames is a
    # NEW corpus beside the OLD metadata — load_corpus_snapshot's
    # shape-vs-metadata check turns a size-changing tear into a loud
    # boot error instead of silently serving a mixed snapshot.
    arrays_path = os.path.join(out_dir, INDEX_ARRAYS_FILE)
    meta_path = os.path.join(out_dir, INDEX_METADATA_FILE)
    # np.savez force-appends '.npz' to names missing it — keep the tmp
    # name's suffix so the path savez writes IS the path we rename
    tmp_arrays = os.path.join(out_dir, f".tmp-{os.getpid()}-corpus.npz")
    tmp_meta = meta_path + f".tmp-{os.getpid()}"
    try:
        np.savez(tmp_arrays, emb=emb)
        with open(tmp_meta, "w") as fh:
            json.dump(meta, fh, indent=2, sort_keys=True)
        os.replace(tmp_arrays, arrays_path)
        os.replace(tmp_meta, meta_path)
    finally:
        for leftover in (tmp_arrays, tmp_meta):
            if os.path.exists(leftover):
                os.unlink(leftover)
    return out_dir


def load_corpus_snapshot(snap_dir: str) -> tuple[dict, np.ndarray]:
    """Read a corpus snapshot -> (metadata dict, (N, D) f32 corpus)."""
    with open(os.path.join(snap_dir, INDEX_METADATA_FILE)) as fh:
        meta = json.load(fh)
    if meta.get("format_version") != INDEX_FORMAT_VERSION:
        raise ValueError(
            f"corpus snapshot format {meta.get('format_version')!r} "
            f"unsupported (this build reads {INDEX_FORMAT_VERSION})")
    with np.load(os.path.join(snap_dir, INDEX_ARRAYS_FILE)) as z:
        emb = np.ascontiguousarray(z["emb"], dtype=np.float32)
    if emb.shape != (meta["size"], meta["dim"]):
        raise ValueError(f"snapshot corpus shape {emb.shape} disagrees "
                         f"with its metadata ({meta['size']}, "
                         f"{meta['dim']}) — truncated or mixed artifact")
    return meta, emb


def _key_name(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _flatten(tree, prefix: str) -> dict[str, np.ndarray]:
    """Pytree -> {'prefix/path/to/leaf': np.ndarray}."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join([prefix] + [_key_name(p) for p in path])
        out[key] = np.asarray(leaf)
    return out


def _stored(v: np.ndarray) -> np.ndarray:
    """A leaf as the ``.npz`` holds it: bfloat16 as its bits (a view)."""
    return v.view(np.uint16) if v.dtype.name == "bfloat16" else v


def _restored(v: np.ndarray, dtype_name: Optional[str]) -> np.ndarray:
    """Inverse of :func:`_stored`, by the leaf's ``array_dtypes`` entry
    (None: an export from before the manifest, all as stored)."""
    if dtype_name == "bfloat16" and v.dtype == np.uint16:
        import ml_dtypes

        return v.view(ml_dtypes.bfloat16)
    return v


def _unflatten(arrays: dict[str, np.ndarray], prefix: str) -> dict:
    """Inverse of :func:`_flatten` for dict-shaped trees (flax params /
    batch_stats are nested string-keyed dicts)."""
    root: dict = {}
    for key, value in arrays.items():
        parts = key.split("/")
        if parts[0] != prefix:
            continue
        node = root
        for p in parts[1:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return root


def _artifact_metadata(model_cfg, *, max_words: int, video_shape,
                       step: int, source: str, arrays: dict,
                       format_version: int, text_lm=None,
                       text_hybrid=None, text_dlm=None) -> dict:
    """Shared metadata assembly for the float and quantized formats:
    sanitized model config (and the language model's group, where the
    sentence tower is one), tokenizer contract, video shape and the
    per-array dtype manifest."""
    from milnce_tpu.config import parse_conv_impl_map

    model_meta = dataclasses.asdict(model_cfg)
    model_meta["word2vec_path"] = ""        # table already lives in params
    impl_map = parse_conv_impl_map(model_meta.get("conv_impl_map", ""))
    model_meta["conv_impl_map"] = ",".join(  # resolve file specs inline
        f"{s}={i}" for s, i in sorted(impl_map.items()))
    token_dict = model_meta.pop("token_dict_path", "")
    group = {"lm": ("text_lm", text_lm),
             "hybrid": ("text_hybrid", text_hybrid),
             "dlm": ("text_dlm", text_dlm)}.get(
                 model_meta.get("text_tower"))
    lm_meta = {group[0]: dataclasses.asdict(group[1])} if group else {}
    return {
        "format_version": int(format_version),
        "generator": "milnce-export (milnce_tpu/serving/export.py)",
        "step": int(step),
        "source_checkpoint": source,
        "model": model_meta,
        **lm_meta,
        "tokenizer": {"max_words": int(max_words),
                      "vocab_size": int(model_meta["vocab_size"]),
                      "token_dict_path": token_dict},
        "video_shape": [int(d) for d in video_shape],
        "param_bytes": int(sum(v.nbytes for v in arrays.values())),
        # per-array dtype manifest: the on-disk precision contract a
        # loader (and scripts/precision_audit.py's quant-readiness
        # report) can audit without opening the npz — each leaf's own
        # type (bfloat16 leaves lie in the npz as their bits)
        "array_dtypes": {k: str(v.dtype) for k, v in arrays.items()},
    }


def export_inference_checkpoint(out_dir: str, params, batch_stats,
                                model_cfg, *, max_words: int,
                                video_shape, step: int = 0,
                                source: str = "", text_lm=None,
                                text_hybrid=None, text_dlm=None) -> str:
    """Write the frozen artifact; returns ``out_dir``.

    ``model_cfg`` is a ``milnce_tpu.config.ModelConfig``; host-specific
    fields (word2vec/token-dict paths, impl-map file paths) are
    sanitized so the artifact is self-contained.  ``text_lm`` /
    ``text_hybrid`` / ``text_dlm``: the ``TextLMConfig`` /
    ``TextHybridConfig`` / ``TextDLMConfig`` of a ``text_tower='lm'`` /
    ``'hybrid'`` / ``'dlm'`` model.  Every leaf is
    written in its own type (module docstring), none copied to another."""
    os.makedirs(out_dir, exist_ok=True)
    arrays = _flatten(params, "params")
    arrays.update(_flatten(batch_stats, "batch_stats"))
    arrays = {k: (v.astype(np.float32) if v.dtype.name == "float64" else v)
              for k, v in arrays.items()}
    np.savez(os.path.join(out_dir, ARRAYS_FILE),
             **{k: _stored(v) for k, v in arrays.items()})
    meta = _artifact_metadata(model_cfg, max_words=max_words,
                              video_shape=video_shape, step=step,
                              source=source, arrays=arrays,
                              format_version=FORMAT_VERSION,
                              text_lm=text_lm, text_hybrid=text_hybrid,
                              text_dlm=text_dlm)
    with open(os.path.join(out_dir, METADATA_FILE), "w") as fh:
        json.dump(meta, fh, indent=2, sort_keys=True)
    return out_dir


def export_quantized_checkpoint(out_dir: str, qvariables, model_cfg, *,
                                max_words: int, video_shape,
                                step: int = 0, source: str = "",
                                calibration: dict | None = None) -> str:
    """Write a quantized edge-tier artifact; returns ``out_dir``.

    ``qvariables`` is ``quant.quantize_variables`` output:
    ``{'params': <int8 where quantized>, 'batch_stats': <f32>,
    'quant_scales': {'params/<path>': f32 scale}}``.  int8 leaves ship
    bit-exact (pinned by the round-trip test); float leaves coerce to
    f32 exactly like the v1 format.  ``calibration`` is the JSON-safe
    block ``quant.calibrate.calibrate_and_quantize`` returns."""
    os.makedirs(out_dir, exist_ok=True)
    arrays = _flatten(qvariables["params"], "params")
    arrays.update(_flatten(qvariables["batch_stats"], "batch_stats"))
    arrays = {k: (v if v.dtype == np.int8 else
                  (v.astype(np.float32)
                   if np.issubdtype(v.dtype, np.floating) else v))
              for k, v in arrays.items()}
    scales = qvariables.get("quant_scales", {})
    for key, scale in scales.items():
        arrays[f"{SCALES_PREFIX}/{key}"] = np.asarray(scale, np.float32)
    np.savez(os.path.join(out_dir, ARRAYS_FILE), **arrays)
    meta = _artifact_metadata(model_cfg, max_words=max_words,
                              video_shape=video_shape, step=step,
                              source=source, arrays=arrays,
                              format_version=QUANT_FORMAT_VERSION)
    meta["quant"] = {
        "scheme": "symmetric-int8",
        "n_quantized": len(scales),
        "per_channel": sorted(
            k for k, s in scales.items() if np.asarray(s).ndim),
        "calibration": calibration or {},
    }
    with open(os.path.join(out_dir, METADATA_FILE), "w") as fh:
        json.dump(meta, fh, indent=2, sort_keys=True)
    return out_dir


def read_export_metadata(export_dir: str) -> dict:
    """Metadata alone (no arrays): how a loader decides which format
    family an artifact is before touching the npz."""
    with open(os.path.join(export_dir, METADATA_FILE)) as fh:
        return json.load(fh)


def load_quantized_checkpoint(export_dir: str) -> tuple[dict, dict]:
    """Read a quantized export -> (metadata, ``{'params',
    'batch_stats', 'quant_scales'}`` variables tree).  Every array is
    checked against the on-disk ``array_dtypes`` manifest — the
    bit-exactness contract is only as good as the dtype it round-trips
    at."""
    meta = read_export_metadata(export_dir)
    if meta.get("format_version") != QUANT_FORMAT_VERSION:
        raise ValueError(
            f"quantized export format {meta.get('format_version')!r} "
            f"unsupported (this build reads {QUANT_FORMAT_VERSION})")
    meta["model"].pop("token_dict_path", None)
    with np.load(os.path.join(export_dir, ARRAYS_FILE)) as z:
        arrays = {k: z[k] for k in z.files}
    manifest = meta.get("array_dtypes", {})
    for key, value in arrays.items():
        want = manifest.get(key)
        if want is not None and str(value.dtype) != want:
            raise ValueError(f"array {key!r} is {value.dtype}, manifest "
                             f"says {want} — corrupt or rewritten npz")
    prefix = SCALES_PREFIX + "/"
    scales = {k[len(prefix):]: v for k, v in arrays.items()
              if k.startswith(prefix)}
    return meta, {"params": _unflatten(arrays, "params"),
                  "batch_stats": _unflatten(arrays, "batch_stats"),
                  "quant_scales": scales}


def load_inference_checkpoint(export_dir: str) -> tuple[dict, dict]:
    """Read an export -> (metadata dict, ``{'params', 'batch_stats'}``
    variables tree of host numpy arrays)."""
    with open(os.path.join(export_dir, METADATA_FILE)) as fh:
        meta = json.load(fh)
    if meta.get("format_version") != FORMAT_VERSION:
        hint = (" — a quantized artifact; load with "
                "load_quantized_checkpoint"
                if meta.get("format_version") == QUANT_FORMAT_VERSION
                else "")
        raise ValueError(f"export format {meta.get('format_version')!r} "
                         f"unsupported (this build reads {FORMAT_VERSION}"
                         f"){hint}")
    # ModelConfig round-trips through JSON minus the serve-sanitized field
    meta["model"].pop("token_dict_path", None)
    manifest = meta.get("array_dtypes", {})
    with np.load(os.path.join(export_dir, ARRAYS_FILE)) as z:
        arrays = {k: _restored(z[k], manifest.get(k)) for k in z.files}
    return meta, {"params": _unflatten(arrays, "params"),
                  "batch_stats": _unflatten(arrays, "batch_stats")}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _restore_inference_subset(checkpoint_dir: str,
                              epoch: Optional[int]) -> tuple[int, dict]:
    """(step, {'params', 'batch_stats'}) from a training run directory —
    metadata-templated restore, so no model build and no optimizer I/O."""
    from milnce_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(checkpoint_dir, create=False)
    try:
        label, raw = mgr.restore_raw(epoch,
                                     subtrees={"step", "params",
                                               "batch_stats"})
    finally:
        mgr.close()
    if not isinstance(raw, dict):           # TrainState restored as object
        raw = {"step": raw.step, "params": raw.params,
               "batch_stats": raw.batch_stats}
    step = int(np.asarray(raw["step"])) if "step" in raw else int(label)
    return step, {"params": raw["params"],
                  "batch_stats": raw.get("batch_stats", {})}


def main(argv=None) -> None:
    from milnce_tpu.config import PRESETS, _add_dataclass_args

    ap = argparse.ArgumentParser(
        description="Export a params-only inference checkpoint "
                    "(milnce_tpu/serving/export.py)")
    ap.add_argument("--checkpoint_dir", required=True,
                    help="training run directory (Orbax)")
    ap.add_argument("--out", required=True, help="export directory to write")
    ap.add_argument("--epoch", type=int, default=None,
                    help="checkpoint label to export (default: latest)")
    ap.add_argument("--preset", choices=sorted(PRESETS), default="full",
                    help="model/data config the run was trained with")
    base = PRESETS["full"]()
    _add_dataclass_args(ap, "model.", base.model)
    _add_dataclass_args(ap, "data.", base.data)
    ns = ap.parse_args(argv)

    cfg = PRESETS[ns.preset]()
    for key, val in vars(ns).items():
        if "." in key and val is not None:
            section, _, fname = key.partition(".")
            setattr(getattr(cfg, section), fname, val)

    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    step, tree = _restore_inference_subset(ns.checkpoint_dir, ns.epoch)
    video_shape = (cfg.data.num_frames, cfg.data.video_size,
                   cfg.data.video_size, 3)
    out = export_inference_checkpoint(
        ns.out, tree["params"], tree["batch_stats"], cfg.model,
        max_words=cfg.data.max_words, video_shape=video_shape, step=step,
        source=os.path.abspath(ns.checkpoint_dir))
    meta_path = os.path.join(out, METADATA_FILE)
    print(f"exported step {step} -> {out} ({meta_path})")


if __name__ == "__main__":
    main()
