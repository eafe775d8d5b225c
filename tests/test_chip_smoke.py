"""CPU rehearsal of ``chip_smoke.py``: its phases, through the same
functions and the same entry points, at the ``tiny`` preset on the
virtual CPU devices — so a wrong path, argument or control flow is found
here and not on the chip.  The real script must refuse to start off the
TPU; what only the chip can show (compiled kernels, device memory,
times) is not asked for here.
"""

import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


def test_full_sizes_are_the_full_width_recipe():
    """Only the batch and the step count may be cut: the smoke's real
    sizes name the full preset untouched, 32f@224, K=5, 20 words."""
    s = chip_smoke.FULL_SIZES
    assert s.preset == "full" and s.model_overrides == ()
    assert (s.frames, s.size, s.candidates, s.words) == (32, 224, 5, 20)
    from milnce_tpu.config import full_preset

    m = full_preset().model
    assert (m.inception_blocks, m.embedding_dim, m.vocab_size,
            m.word_embedding_dim, m.text_hidden_dim) == (
                9, 512, 66250, 300, 2048)
    assert m.gating is True


def test_phases_rehearsed_at_tiny_on_the_cpu(tmp_path, capsys):
    """Phases 1-4 in order: trainer + resume, sdtw_3 trainer + kernel
    parity, chunked MIL-NCE parity, export + server + socket queries
    against numpy — any failing phase raises."""
    chip_smoke.run_phases(chip_smoke.TINY_SIZES, "cpu", str(tmp_path))
    out = capsys.readouterr().out
    assert "trainer (resumed): step 6" in out
    assert "/healthz: engine recompiles 0" in out
    assert '"ok"' not in out            # only main() prints the result


def test_data_parallel_phase_rehearsed_on_four_virtual_devices(tmp_path,
                                                               capsys):
    """The ``--chips 4`` phase on four of the eight virtual CPU devices:
    the 4-device SGD step equals the 1-device step leaf for leaf, every
    device holds a batch shard, the collectives are in the compiled
    text."""
    assert len(jax.devices()) >= 4
    chip_smoke.phase_data_parallel(chip_smoke.TINY_SIZES, "cpu",
                                   str(tmp_path), n_devices=4)
    out = capsys.readouterr().out
    assert "batch shards on devices [0, 1, 2, 3]" in out
    assert "all-gather and all-reduce present" in out


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one-chip", "chips-4"])
def test_real_script_refuses_to_start_off_the_tpu(args):
    """`JAX_PLATFORMS=cpu python chip_smoke.py`: nonzero exit, and no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        env=env, cwd=_REPO, capture_output=True, timeout=300)
    assert proc.returncode != 0
    assert b'"ok"' not in proc.stdout, proc.stdout
    assert b"no TPU" in proc.stderr


def test_partition_devices_on_a_real_backend_splits_evenly(monkeypatch):
    """serving/pool.py's branch for every backend that is not the CPU —
    never taken by a CPU test: contiguous even groups, an uneven split
    refused."""
    from milnce_tpu.serving.pool import ReplicaPool

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    devs = list(range(4))
    assert ReplicaPool.partition_devices(devs, 2) == [[0, 1], [2, 3]]
    assert ReplicaPool.partition_devices(devs, 4) == [[0], [1], [2], [3]]
    assert ReplicaPool.partition_devices(devs, 1) == [devs]
    with pytest.raises(ValueError, match="do not split evenly"):
        ReplicaPool.partition_devices(devs, 3)
