"""Operations and bytes from shapes.

The S3D-G part is a copy (not an import) of the arithmetic in
``milnce_tpu/utils/roofline.py`` (``train_step_flops``,
``video_fwd_flops``, ``text_fwd_flops``; pinned against XLA's cost
analysis in ``tests/test_roofline.py``), so that no later PR can move
the yardstick; ``benchmarks/tests/test_flops.py`` checks that the two
agree today.  Convolution and dense FLOPs are exact (2 x outputs x
fan-in over the valid taps); element-wise work is not counted, and no
recomputation is counted.  New here: the index scan's bytes and FLOPs.
"""

from __future__ import annotations

INCEPTION_PLAN = [
    (64, 96, 128, 16, 32, 32), (128, 128, 192, 32, 96, 64),
    (192, 96, 208, 16, 48, 64), (160, 112, 224, 24, 64, 64),
    (128, 128, 256, 24, 64, 64), (112, 144, 288, 32, 64, 64),
    (256, 160, 320, 32, 128, 128), (256, 160, 320, 32, 128, 128),
    (384, 192, 384, 48, 128, 128),
]
POOLS_BEFORE = {2: (2, 2, 2), 7: (2, 2, 2)}     # strides of the pools


def _valid_taps(size: int, k: int, s: int):
    """(outputs, kernel taps that meet real input) along one dimension
    with symmetric padding k // 2: products with the padding are no work."""
    pad = k // 2
    out = (size + 2 * pad - k) // s + 1
    taps = 0
    for o in range(out):
        start = o * s - pad
        taps += min(start + k, size) - max(start, 0)
    return out, taps


def _conv(shape, cout, kernel, stride):
    """-> (output shape, forward FLOPs) of one convolution."""
    b, t, h, w, c = shape
    (ot, vt), (oh, vh), (ow, vw) = (
        _valid_taps(n, k, s) for n, k, s in zip((t, h, w), kernel, stride))
    return (b, ot, oh, ow, cout), 2.0 * b * c * cout * vt * vh * vw


def _sep_conv(shape, cout, k=3):
    shape, f1 = _conv(shape, cout, (1, k, k), (1, 1, 1))
    shape, f2 = _conv(shape, cout, (k, 1, 1), (1, 1, 1))
    return shape, f1 + f2


def _pool(shape, stride):
    b, t, h, w, c = shape
    return (b, -(-t // stride[0]), -(-h // stride[1]), -(-w // stride[2]), c)


def video_fwd_flops(batch: int, frames: int, size: int, blocks: int = 9,
                    embedding_dim: int = 512) -> float:
    one = (1, 1, 1)
    shape, total = _conv((batch, frames, size, size, 3), 64, (3, 7, 7),
                         (2, 2, 2))
    shape = _pool(shape, (1, 2, 2))
    shape, f = _conv(shape, 64, one, one)
    total += f
    shape, f = _sep_conv(shape, 192)
    total += f
    shape = _pool(shape, (1, 2, 2))
    for idx, (c0, c1a, c1b, c2a, c2b, c3b) in enumerate(
            INCEPTION_PLAN[:blocks]):
        if idx in POOLS_BEFORE:
            shape = _pool(shape, POOLS_BEFORE[idx])
        b, t, h, w, _ = shape
        for cout in (c0, c3b):
            total += _conv(shape, cout, one, one)[1]
        for ca, cb in ((c1a, c1b), (c2a, c2b)):
            mid, f = _conv(shape, ca, one, one)
            total += f + _sep_conv(mid, cb)[1]
        cout = c0 + c1b + c2b + c3b
        total += 2.0 * b * cout * cout * 4      # the four gating denses
        shape = (b, t, h, w, cout)
    return total + 2.0 * batch * shape[-1] * embedding_dim


def text_fwd_flops(rows: int, words: int, word_dim: int = 300,
                   hidden: int = 2048, embedding_dim: int = 512) -> float:
    return (2.0 * rows * words * word_dim * hidden
            + 2.0 * rows * hidden * embedding_dim)


def train_step_flops(batch: int, frames: int, size: int, candidates: int,
                     words: int, blocks: int = 9, embedding_dim: int = 512,
                     word_dim: int = 300, hidden: int = 2048) -> float:
    """Forward + backward of one step over ``batch`` clips: backward of a
    conv or dense stack is twice its forward; the MIL-NCE logits matmul
    (batch x batch x K x D) rides on top.  Nothing recomputed counts."""
    model = (video_fwd_flops(batch, frames, size, blocks, embedding_dim)
             + text_fwd_flops(batch * candidates, words, word_dim, hidden,
                              embedding_dim))
    return 3.0 * model + 3.0 * 2.0 * batch * batch * candidates * embedding_dim


def index_scan_work(rows: int, dim: int, queries: int,
                    row_bytes_per_elem: int = 4) -> dict:
    """One pass of ``queries`` over an index of ``rows`` x ``dim``: every
    row is read once (its own bytes; the scores and the top-k are not
    counted, whatever implements them) and every (query, row) pair costs
    2 x dim operations.  Source: the definition of a brute-force scan."""
    return {"bytes": float(rows) * dim * row_bytes_per_elem,
            "flops": 2.0 * queries * rows * dim}


def least_time_s(work: dict, peaks: dict) -> tuple:
    """Roofline: -> (least seconds, which bound holds)."""
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    by_flops = work["flops"] / peaks["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
