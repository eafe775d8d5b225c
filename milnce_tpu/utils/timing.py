"""On-device timing of a small kernel, free of per-dispatch host cost.

A kernel of microseconds timed one dispatch at a time measures the
host's dispatch latency, not the kernel.  The protocol here, shared by
the ``bench.py``-adjacent harnesses
(``milnce_tpu/ops/softdtw_profile.py``, ``scripts/stage_probe.py``):

1. run ``k`` executions inside ONE XLA program (a ``lax.scan`` whose
   carry perturbs the input by ±1e-30, defeating CSE; the perturbation
   is cast to the input dtype so bf16 workloads aren't silently promoted
   to f32);
2. materialize the scalar result ON HOST (the device->host transfer of
   the computed value is the sync);
3. report the difference ``(T(k1+n) - T(k1)) / n``, which cancels the
   fixed dispatch cost.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def chained_seconds(step: Callable, x, n_iters: int, k1: int = 16,
                    reps: int = 2) -> float:
    """Seconds per execution of ``step(x) -> scalar`` under the protocol
    above.  ``step`` must be a pure jittable function of one array."""

    def chain(k):
        def run(d):
            def body(acc, _):
                bump = (acc * 1e-30).astype(d.dtype)
                return acc + jnp.asarray(step(d + bump),
                                         jnp.float32), None

            return lax.scan(body, jnp.float32(0.0), None, length=k)[0]

        return jax.jit(run)

    f1, f2 = chain(k1), chain(k1 + n_iters)
    float(f1(x)), float(f2(x))                  # compile + warm
    t1 = min(_wall(f1, x) for _ in range(reps))
    t2 = min(_wall(f2, x) for _ in range(reps))
    return max(t2 - t1, 0.0) / n_iters


def _wall(f, x) -> float:
    # graftlint: disable=GL005(the float() host materialization below IS the sync — step 2 of the differenced protocol)
    t0 = time.perf_counter()
    float(f(x))                                 # host materialization
    return time.perf_counter() - t0
