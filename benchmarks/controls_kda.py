"""Controls of a cell whose tower has KDA layers, put in the served place
and judged as a run is (``harness.judge`` against the cell's limits):
``serve_tower.control``'s kinds (``program``, ``float8``, ``unrelated``)
and ``state_dropped``, the program whose delta rule starts every chunk from
a zero state (each chunk a row of its own), replayed by the sound one.
``serve_tower.state_dropped`` does the same to the Mamba-2 scan.

    python3 -m benchmarks.controls_kda --workload <cell> --seeds 1 2 \\
        --kinds program float8 state_dropped

reads each control at the cell's own sizes, without a serving window, on
``compare_sample`` queries drawn from the seed's traffic as a run draws
them (the longest, and ``compare_long``'s, always among them), and prints
one JSON line a (seed, kind): the compared numbers, each with its limit,
and whether they pass.  One process; it takes the chip itself.
"""

from __future__ import annotations

import contextlib
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def state_dropped():
    """The program's delta rule with the state that a chunk hands the next
    one DROPPED.  Controls only: the benchmark swaps the function the tower
    calls; the program has no such option."""
    from milnce_tpu.models import text_hybrid

    sound = text_hybrid.kda_chunked

    def each_chunk_alone(q, k, v, g, beta, *, chunk, scale, **kw):
        import jax.numpy as jnp

        rows, s = q.shape[:2]
        pad = -s % chunk

        def cut(t):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape((rows * ((s + pad) // chunk), chunk)
                             + t.shape[2:])

        o = sound(cut(q), cut(k), cut(v), cut(g), cut(beta), chunk=chunk,
                  scale=scale, **kw)
        return o.reshape((rows, s + pad) + o.shape[2:])[:, :s]

    text_hybrid.kda_chunked = each_chunk_alone
    try:
        yield
    finally:
        text_hybrid.kda_chunked = sound


def control(cell, seed: int, tokens, kind: str) -> dict:
    """``serve_tower.control`` with ``state_dropped`` for the delta rule
    -> ``compared``."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_lm, serve_tower
    from benchmarks.reference import retrieval as reference

    if kind != "state_dropped":
        return serve_tower.control(cell, seed, tokens, kind)
    replays = serve_tower.program_routing(cell, seed, tokens)
    with state_dropped():
        served = serve_tower.program_routing(cell, seed, tokens)[-1]["emb"]
    k = int(cell.config["serve"]["topk"])
    top = reference.scan(jnp.asarray(served, jnp.float32),
                         serve_lm.corpus_blocks(seed, cell.config["index"]),
                         np.zeros((len(tokens), k), np.int64), k)
    return serve_tower.judged(cell, seed, tokens, top["top_idx"],
                              top["top_scores"], replays)


def sample(cell, seed: int):
    """``compare_sample`` queries of the seed's traffic, as a run picks
    them from what it answered."""
    from benchmarks import traffic_gen
    from benchmarks.drivers import serve_tower

    traffic = cell.traffic
    pool = traffic_gen.query_pool(seed, traffic, cell.config["vocab_size"],
                                  cell.config["data"]["max_words"])
    lengths = (pool != 0).sum(axis=1)
    return pool[serve_tower.compared_sample(seed, lengths, traffic)]


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kinds", nargs="+", required=True,
                    choices=("program", "float8", "state_dropped",
                             "unrelated"))
    args = ap.parse_args(argv)
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    os.chdir(CHECKOUT)
    from benchmarks import harness
    from milnce_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cell = harness.load_cell(harness.load_benchmark(CHECKOUT), args.workload)
    for seed in args.seeds:
        tokens = sample(cell, seed)
        for kind in args.kinds:
            compared = control(cell, seed, tokens, kind)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind, "passes": harness.judge(compared),
                              "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
