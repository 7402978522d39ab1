"""Training-substrate tests: optimizer, loss descent, checkpointing,
gradient compression, microbatching, elasticity hooks."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.config import ParallelConfig, TrainConfig
from repro.configs import llama32_1b
from repro.models import model as M
from repro.training import checkpoint as ckpt
from repro.training import compression, data
from repro.training import optimizer as opt
from repro.training import train_step as ts

PCFG = ParallelConfig(compute_dtype="float32")


def small_setup(seed=0, seq=64, batch=4):
    cfg = llama32_1b.reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    state = opt.init_opt_state(params)
    pipe = data.SyntheticLM(cfg.vocab, seq, batch, seed=seed)
    return cfg, params, state, pipe


def test_loss_decreases_over_steps():
    cfg, params, state, pipe = small_setup()
    tcfg = TrainConfig(seq_len=64, global_batch=4, lr=1e-3, steps=60,
                       warmup=5)
    step, _, _ = ts.make_train_step(cfg, PCFG, tcfg, mesh=None)
    fn = jax.jit(step)
    losses = []
    for i in range(60):
        batch = jax.tree.map(jnp.asarray, pipe.batch(i))
        params, state, metrics = fn(params, state, batch)
        losses.append(float(metrics["loss"]))
    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    assert last < first - 0.1, (first, last)


def test_microbatch_matches_full_batch_gradients():
    """Grad accumulation over microbatches == single big batch (same data)."""
    cfg, params, state, pipe = small_setup(seed=3)
    batch = jax.tree.map(jnp.asarray, pipe.batch(0))
    t_full = TrainConfig(seq_len=64, global_batch=4, microbatch=0,
                         lr=1e-3)
    t_micro = TrainConfig(seq_len=64, global_batch=4, microbatch=2,
                          lr=1e-3)
    s_full, _, _ = ts.make_train_step(cfg, PCFG, t_full, mesh=None)
    s_micro, _, _ = ts.make_train_step(cfg, PCFG, t_micro, mesh=None)
    p1, _, m1 = jax.jit(s_full)(params, state, batch)
    p2, _, m2 = jax.jit(s_micro)(params, state, batch)
    # same direction updates: params close (loss averaging differs at the
    # margin by masking, so allow small tolerance)
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, p2)))
    assert d < 5e-4, d


def test_adamw_weight_decay_pulls_to_zero():
    ocfg = opt.AdamWConfig(lr=0.1, weight_decay=0.5, warmup=0,
                           total_steps=10, grad_clip=1e9)
    params = {"w": jnp.ones((4,))}
    state = opt.init_opt_state(params)
    grads = {"w": jnp.zeros((4,))}
    p, state, _ = opt.adamw_update(ocfg, params, grads, state)
    assert float(p["w"][0]) < 1.0


def test_checkpoint_roundtrip_and_resume(tmp_path):
    cfg, params, state, pipe = small_setup(seed=1)
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, {"params": params, "opt": state})
    assert ckpt.latest_step(d) == 7
    restored = ckpt.restore(d, 7, {"params": params, "opt": state})
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_ignores_uncommitted(tmp_path):
    d = str(tmp_path / "ck")
    os.makedirs(os.path.join(d, "step_00000009"))  # no _COMMITTED marker
    ckpt.save(d, 3, {"x": jnp.ones(2)})
    assert ckpt.latest_step(d) == 3


def test_checkpoint_keep_k(tmp_path):
    d = str(tmp_path / "ck")
    for s in (1, 2, 3, 4, 5):
        ckpt.save(d, s, {"x": jnp.ones(1) * s}, keep=2)
    assert ckpt.latest_step(d) == 5
    steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert len(steps) == 2


def test_checkpoint_missing_is_typed(tmp_path):
    d = str(tmp_path / "ck")
    with pytest.raises(ckpt.CheckpointMissing):
        ckpt.restore(d, 3, {"x": jnp.ones(2)})
    # absence is a subtype of CheckpointError, so one except clause works
    assert issubclass(ckpt.CheckpointMissing, ckpt.CheckpointError)


def test_checkpoint_restore_rejects_shape_mismatch(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"x": jnp.ones((4, 2))})
    with pytest.raises(ckpt.CheckpointError, match="shape"):
        ckpt.restore(d, 1, {"x": jnp.ones((4, 3))})
    with pytest.raises(ckpt.CheckpointError, match="leaves"):
        ckpt.restore(d, 1, {"x": jnp.ones((4, 2)), "y": jnp.ones(1)})


def test_checkpoint_restore_rejects_corruption(tmp_path):
    d = str(tmp_path / "ck")
    path = ckpt.save(d, 1, {"x": jnp.ones(3)})
    os.remove(os.path.join(path, "shard_h000.npz"))
    with pytest.raises(ckpt.CheckpointError, match="shard"):
        ckpt.restore(d, 1, {"x": jnp.ones(3)})
    with open(os.path.join(path, "manifest.json"), "w") as f:
        f.write("{not json")
    with pytest.raises(ckpt.CheckpointError, match="JSON"):
        ckpt.load_manifest(d, 1)


def test_checkpoint_meta_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    meta = {"family": "d15", "p": 8, "coo_digest": "abc123"}
    ckpt.save(d, 2, {"x": jnp.ones(2)}, meta=meta)
    assert ckpt.load_manifest(d, 2)["meta"] == meta


def test_exact_resume_reproduces_run(tmp_path):
    """Train 10 steps; vs train 5, checkpoint, restore, train 5 more."""
    cfg, params, state, pipe = small_setup(seed=2)
    tcfg = TrainConfig(seq_len=64, global_batch=4, lr=1e-3, steps=20)
    step, _, _ = ts.make_train_step(cfg, PCFG, tcfg, mesh=None)
    fn = jax.jit(step)

    pA, sA = params, state
    for i in range(10):
        b = jax.tree.map(jnp.asarray, pipe.batch(i))
        pA, sA, _ = fn(pA, sA, b)

    pB, sB = params, state
    for i in range(5):
        b = jax.tree.map(jnp.asarray, pipe.batch(i))
        pB, sB, _ = fn(pB, sB, b)
    d = str(tmp_path / "ck")
    ckpt.save(d, 5, {"params": pB, "opt": sB})
    tree = ckpt.restore(d, 5, {"params": pB, "opt": sB})
    pB, sB = tree["params"], tree["opt"]
    for i in range(5, 10):
        b = jax.tree.map(jnp.asarray, pipe.batch(i))
        pB, sB, _ = fn(pB, sB, b)

    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_int8_compression_error_feedback_converges():
    """Quantize-with-feedback: accumulated updates track the true sum."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal((1024,)) * 1e-3, jnp.float32)
    err = None
    acc = np.zeros(1024)
    for _ in range(50):
        q, scale, meta = compression.quantize_int8(
            g_true + (0 if err is None else err))
        deq = compression.dequantize_int8(q, scale, meta)
        err = (g_true + (0 if err is None else err)) - deq
        acc += np.asarray(deq)
    np.testing.assert_allclose(acc, 50 * np.asarray(g_true),
                               rtol=0.02, atol=2e-4)


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(1)
    for shape in ((17,), (64, 33), (3, 5, 7)):
        g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        q, s, meta = compression.quantize_int8(g)
        deq = compression.dequantize_int8(q, s, meta)
        assert deq.shape == g.shape
        err = np.abs(np.asarray(deq - g))
        assert err.max() <= float(np.abs(np.asarray(g)).max()) / 127 + 1e-6


def test_straggler_monitor_flags():
    from repro.distributed.elastic import StepMonitor
    flagged = []
    mon = StepMonitor(straggler_factor=3.0,
                      on_straggler=lambda s, t, m: flagged.append(s))
    for i in range(10):
        mon.observe(i, 1.0)
    assert not flagged
    assert mon.observe(10, 10.0)
    assert flagged == [10]


def test_resilient_step_retries():
    from repro.distributed.elastic import run_step_resilient
    from repro.distributed.faults import TransientFault
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("preempted")
        return x + 1

    out = run_step_resilient(flaky, None, lambda: (41,), 41, max_retries=5)
    assert out == 42 and calls["n"] == 3


def test_resilient_step_does_not_retry_caller_bugs():
    from repro.distributed.elastic import run_step_resilient
    calls = {"n": 0}

    def buggy(x):
        calls["n"] += 1
        raise TypeError("caller bug, not a device failure")

    with pytest.raises(TypeError):
        run_step_resilient(buggy, None, lambda: (41,), 41, max_retries=5)
    assert calls["n"] == 1


@pytest.mark.parametrize("status,retried", [
    ("UNAVAILABLE: connection to peer lost", True),
    ("RESOURCE_EXHAUSTED: out of memory allocating 2.00G", False),
    ("INTERNAL: Mosaic failed to compile TPU kernel", False),
    ("INTERNAL: during context [hlo verifier]", False),
    ("UNKNOWN: unrecognized status", False),
])
def test_resilient_step_retries_device_faults_only(status, retried):
    """A runtime failure of the device is retried; a compile or memory
    failure would fail the same way again, so it propagates at once."""
    from jax.errors import JaxRuntimeError
    from repro.distributed.elastic import run_step_resilient
    calls = {"n": 0}

    def step(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise JaxRuntimeError(status)
        return x + 1

    if retried:
        assert run_step_resilient(step, None, lambda: (41,), 41) == 42
    else:
        with pytest.raises(JaxRuntimeError):
            run_step_resilient(step, None, lambda: (41,), 41)
    assert calls["n"] == (2 if retried else 1)


def test_synthetic_data_deterministic_and_sharded():
    pipe = data.SyntheticLM(1000, 32, 8, seed=5)
    b1 = pipe.batch(3)
    b2 = pipe.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    lo = pipe.batch(3, lo=2, hi=5)
    np.testing.assert_array_equal(lo["tokens"], b1["tokens"][2:5])
    assert (pipe.batch(4)["tokens"] != b1["tokens"]).any()


def test_bf16_error_feedback_beats_raw_casting():
    """The ErrorFeedback accumulator (the compress="bf16" training-side
    state) keeps the accumulated lossy-step error far below raw
    repeated bf16 casting."""
    g = jnp.asarray(np.linspace(1e-3, 1.0, 1000), jnp.float32)
    ef = compression.ErrorFeedback()
    acc_fb = np.zeros(1000)
    acc_raw = np.zeros(1000)
    for _ in range(50):
        acc_fb += np.asarray(ef(g), np.float64)
        acc_raw += np.asarray(
            compression.from_bf16(compression.to_bf16(g)), np.float64)
    exact = 50 * np.asarray(g, np.float64)
    err_fb = np.abs(acc_fb - exact).max()
    err_raw = np.abs(acc_raw - exact).max()
    assert err_fb < 0.1 * err_raw, (err_fb, err_raw)


def test_bf16_roundtrip_halves_and_restores_dtype():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(64),
                    jnp.float32)
    w = compression.to_bf16(x)
    assert w.dtype == jnp.bfloat16
    back = compression.from_bf16(w)
    assert back.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               rtol=8e-3, atol=8e-3)
