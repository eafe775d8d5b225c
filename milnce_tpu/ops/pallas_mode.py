"""Whether the repo's Pallas kernels run interpreted — decided in ONE place.

Interpret mode is for the ``cpu`` platform only, which is where the
tests and the rehearsals run.  On any other platform a kernel is
compiled for that platform or the call fails: a run that finds no chip
must not pass for a chip run by quietly interpreting every kernel.
Call it through the module (``pallas_mode.interpret()``) so that a test
which compiles for a described TPU can steer it from the test.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    return jax.default_backend() == "cpu"
