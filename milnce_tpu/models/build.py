"""Model factory: ModelConfig -> S3D module (+ optional pretrained word2vec).

Replaces the reference's constructor-side file IO (s3dg.py:235-238, where the
model loads word2vec.pth and dict.npy itself): file loading lives here, the
module stays pure.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from milnce_tpu.config import (TEXT_TOWERS, ModelConfig, TextDLMConfig,
                               TextHybridConfig, TextLMConfig,
                               parse_conv_impl_map)
from milnce_tpu.models.s3dg import S3D
from milnce_tpu.models.text import word2vec_embedding_init


def load_word2vec_table(path: str) -> np.ndarray:
    """Load a pretrained (V, 300) embedding table from .npy/.npz, or from
    the reference's torch-saved ``word2vec.pth`` (s3dg.py:159)."""
    if path.endswith((".pth", ".pt", ".tar")):
        import torch

        return torch.load(path, map_location="cpu",
                          weights_only=False).numpy()
    if path.endswith(".npz"):
        with np.load(path) as z:
            return z[list(z.files)[0]]
    return np.load(path)


def build_model(cfg: ModelConfig, bn_axis_name: str | None = None,
                text_lm: TextLMConfig | None = None,
                text_hybrid: TextHybridConfig | None = None,
                text_dlm: TextDLMConfig | None = None) -> S3D:
    """``text_lm`` / ``text_hybrid`` / ``text_dlm``: the language model's
    group, needed (and validated) where ``cfg.text_tower`` is 'lm' /
    'hybrid' / 'dlm'."""
    if cfg.text_tower not in TEXT_TOWERS:
        raise ValueError(f"model.text_tower={cfg.text_tower!r}: one of "
                         f"{', '.join(TEXT_TOWERS)}")
    lm = None
    if cfg.text_tower == "lm":
        from milnce_tpu.models.text_lm import lm_dims

        if text_lm is None:
            raise ValueError("model.text_tower='lm' needs the text_lm group "
                             "(build_model(cfg.model, text_lm=cfg.text_lm))")
        lm = lm_dims(text_lm)
    hybrid = None
    if cfg.text_tower == "hybrid":
        from milnce_tpu.models.text_hybrid import hybrid_dims

        if text_hybrid is None:
            raise ValueError(
                "model.text_tower='hybrid' needs the text_hybrid group "
                "(build_model(cfg.model, text_hybrid=cfg.text_hybrid))")
        hybrid = hybrid_dims(text_hybrid)
    dlm = None
    if cfg.text_tower == "dlm":
        from milnce_tpu.models.text_dlm import dlm_dims

        if text_dlm is None:
            raise ValueError(
                "model.text_tower='dlm' needs the text_dlm group "
                "(build_model(cfg.model, text_dlm=cfg.text_dlm))")
        dlm = dlm_dims(text_dlm)
    embedding_init = None
    vocab_size = cfg.vocab_size
    if cfg.word2vec_path and os.path.exists(cfg.word2vec_path):
        table = load_word2vec_table(cfg.word2vec_path)
        vocab_size = table.shape[0]
        embedding_init = word2vec_embedding_init(table)
    return S3D(
        num_classes=cfg.embedding_dim,
        gating=cfg.gating,
        use_space_to_depth=cfg.space_to_depth,
        inception_blocks=cfg.inception_blocks,
        vocab_size=vocab_size,
        word_embedding_dim=cfg.word_embedding_dim,
        text_hidden_dim=cfg.text_hidden_dim,
        weight_init=cfg.weight_init,
        bn_axis_name=bn_axis_name if cfg.sync_batchnorm else None,
        conv_impl=cfg.conv_impl,
        # hashable form (tuple of pairs) so the module stays usable as a
        # static jit argument; S3D turns it back into a lookup
        conv_impl_map=tuple(sorted(
            parse_conv_impl_map(cfg.conv_impl_map).items())) or None,
        embedding_init=embedding_init,
        remat=cfg.remat,
        text_lm=lm,
        text_hybrid=hybrid,
        text_dlm=dlm,
        dtype=jnp.dtype(cfg.dtype),
    )
