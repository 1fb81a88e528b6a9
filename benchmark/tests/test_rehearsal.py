"""CPU rehearsal: each driver's `run` at a tiny fixture configuration,
through the real engine and the real train step (Pallas kernels in
interpret mode). Counts only: a CPU run gives no time, rate or share that
means anything, and `run.py` itself refuses to run here."""
import json
import math
import subprocess
import sys

import pytest

from benchmark.drivers import closed_loop_serve, train_steps
from benchmark.end_to_end import (decode_tokens_per_s, gap_p90_ms, setup_s,
                                  train_tokens_per_s_per_chip, ttft_mean_ms)
from benchmark.layer_metrics import (compiles_in_window, tick_roofline,
                                     train_mfu, train_step_p50_ms)
from benchmark.tests.helpers import ROOT_DIR, context


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(closed_loop_serve, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(train_steps, "memory_peak_bytes", lambda: 0)


def test_serve_driver_rehearsal():
    ctx = context("tiny-serve", "tiny_closed", seed=2**31 + 5, seconds=1.0)
    rec = closed_loop_serve.run(ctx)
    assert rec.correct, rec.notes
    assert rec.notes["positions_judged"] == 18
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["ticks"] == c["engine_steps"] == len(rec.samples["tick_ms"])
    assert c["tokens_out"] == (len(rec.samples["gap_ms"])
                               + len(rec.samples["ttft_ms"]))
    assert c["compiles_in_window"] == compiles_in_window.read(rec) == 0
    for reader in (decode_tokens_per_s, gap_p90_ms, ttft_mean_ms, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) > 0
    assert tick_roofline.read(rec) is None      # no trace, nothing to read


def test_train_driver_rehearsal():
    ctx = context("tiny-train", "tiny_pretrain", seed=7, seconds=1.0)
    rec = train_steps.run(ctx)
    assert rec.correct, rec.notes
    lo, hi = rec.notes["first_loss_band"]
    assert lo == pytest.approx(math.log(256)) and lo <= rec.notes["first_loss"] <= hi
    c = rec.counters
    assert c["steps"] == rec.attempted == len(rec.samples["step_ms"]) > 0
    assert c["tokens"] == c["steps"] * 64 and c["chips"] == 1
    assert c["compiles_in_window"] == 0
    assert train_tokens_per_s_per_chip.read(rec) == c["tokens"] / c["elapsed_s"]
    assert train_step_p50_ms.read(rec) > 0 and train_mfu.read(rec) > 0


def test_run_py_refuses_a_machine_without_the_tpu():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "train_1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT_DIR, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "needs 1 TPU device" in out.stderr
    assert not out.stdout.strip().startswith("{")


MESH_REHEARSAL = """
import json
from benchmark.drivers import train_steps
from benchmark.tests.helpers import context
train_steps.memory_peak_bytes = lambda: 0
rec = train_steps.run(context("tiny-train-pp2tp2", "tiny_pretrain_mb2",
                              seed=2**31 + 7, seconds=0.5))
print(json.dumps({"correct": rec.correct, "counters": rec.counters}))
"""


def test_train_driver_rehearsal_on_a_pp2_tp2_mesh():
    """The four-chip cell's mesh on four virtual CPU devices (a process
    of its own: the device count is fixed when JAX starts)."""
    out = subprocess.run(
        [sys.executable, "-c", MESH_REHEARSAL], cwd=ROOT_DIR,
        capture_output=True, text=True, timeout=600,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"]
    c = got["counters"]
    assert c["chips"] == 4 and c["tokens"] == c["steps"] * 128 > 0
    assert c["compiles_in_window"] == 0


def test_training_reference_agrees_with_the_program_at_a_tiny_size():
    """The plain float32 loss beside the program's bf16 loss on the same
    seeded weights and tokens: a tolerance of bf16's 2^-8 relative
    rounding through two blocks and the head."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference
    from benchmark.lib.program import llama_config
    from benchmark.tests.helpers import fixture
    from paddle_tpu.models import llama as L

    cfg = fixture("configs", "tiny-train")
    lcfg = llama_config(cfg, jnp.float32)
    params = L.init_params(lcfg, jax.random.PRNGKey(3))
    data = jax.random.randint(jax.random.PRNGKey(4), (33,), 0, 256, jnp.int32)
    ours = float(L.loss_fn(params, data[None, :-1], data[None, 1:], lcfg,
                           attn_impl="xla"))
    with jax.default_matmul_precision("highest"):
        ref = float(reference.loss(
            params, data[:-1], data[1:], heads=4, kv_heads=2,
            theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"]))
    assert abs(ours - ref) <= 2.0 ** -7 * ref, (ours, ref)
