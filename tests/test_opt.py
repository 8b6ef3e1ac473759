"""mx.analysis.opt — cost-model-guided auto-optimization tests.

Covers the ISSUE-9 acceptance surface:
- interpret-mode equivalence oracle for every rewrite kind (f32 + bf16,
  odd/prime dims, grad-through-rewrite, bitwise integer paths),
- the no-regression guard (a rewrite predicted as a loss is left
  untouched — the CPU target refuses J001 by construction),
- cost-model sanity + rank correlation against the banked TPU corpus
  (Spearman >= 0.8 on the >= 10-row infer subset),
- autotuner determinism, TunedConfig persistence and fingerprint
  invalidation on env-knob / jaxlib flips,
- Trainer / InferenceEngine consumption of tuned configs,
- zero-retrace guarantee of rewritten callables,
- the opt_bench --quick tier-1 smoke.
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.analysis import opt
from mxnet_tpu.analysis.opt.cost_model import CostModel, spearman
from mxnet_tpu.analysis.opt.rewrites import (_exactly_representable,
                                             check_equivalence,
                                             rewrite_callable)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TPU_MODEL = CostModel.for_backend("tpu", "TPU v5 lite")
CPU_MODEL = CostModel.for_backend("cpu")


def _misaligned_dot(dtype):
    """Compute-bound, tile-misaligned matmul: K=130 pads to 256 (49%
    waste), the J001 planner's bread and butter."""
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(520, 130) * 0.1, dtype)
    w = jnp.asarray(rng.randn(130, 520) * 0.1, dtype)

    def f(x, w):
        return jnp.tanh(x @ w)

    return f, (x, w)


# ---------------------------------------------------------------------------
# tile-pad helpers
# ---------------------------------------------------------------------------
def test_pad_helpers_shapes_and_grad():
    from mxnet_tpu.ops.nn import mxu_pad_amount, pad_to_tile, unpad_slice

    assert mxu_pad_amount(130, 128) == 126
    assert mxu_pad_amount(128, 128) == 0
    x = jnp.ones((10, 130))
    p = pad_to_tile(x, {0: 8, 1: 128})
    assert p.shape == (16, 256)
    assert float(p.sum()) == float(x.sum())          # zero padding
    assert unpad_slice(p, (10, 130)).shape == (10, 130)
    # aligned input is returned untouched (no-op guarantee)
    y = jnp.ones((16, 256))
    assert pad_to_tile(y, {0: 8, 1: 128}) is y
    # vjp of pad is slice: grads land on the original operand
    g = jax.grad(lambda x: pad_to_tile(x, {1: 128}).sum())(x)
    assert g.shape == x.shape
    assert bool((onp.asarray(g) == 1.0).all())


# ---------------------------------------------------------------------------
# J001 equivalence oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rewrite_dot_equivalence(dtype):
    f, args = _misaligned_dot(dtype)
    f2, rep = rewrite_callable(f, *args, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied >= 1, rep.render()
    assert any(d.rule == "J001" for d in rep.applied)
    eq = check_equivalence(f, f2, *args)
    assert eq["equal"], eq


def test_rewrite_dot_odd_prime_dims():
    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randn(520, 131) * 0.1, jnp.float32)   # prime K
    w = jnp.asarray(rng.randn(131, 523) * 0.1, jnp.float32)   # prime N

    def f(x, w):
        return x @ w

    f2, rep = rewrite_callable(f, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    eq = check_equivalence(f, f2, x, w)
    assert eq["equal"], eq


def test_rewrite_int_dot_bitwise():
    rng = onp.random.RandomState(2)
    x = jnp.asarray(rng.randint(-7, 7, (520, 130)), jnp.int32)
    w = jnp.asarray(rng.randint(-7, 7, (130, 520)), jnp.int32)

    def f(x, w):
        return x @ w

    f2, rep = rewrite_callable(f, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    eq = check_equivalence(f, f2, x, w, bitwise=True)
    assert eq["equal"], eq


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rewrite_conv_equivalence(dtype):
    from jax import lax

    rng = onp.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 3, 12, 12) * 0.3, dtype)
    w = jnp.asarray(rng.randn(10, 3, 3, 3) * 0.3, dtype)

    def c(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
        return lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn)

    c2, rep = rewrite_callable(c, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    assert rep.applied[0].kind == "pad_conv"
    eq = check_equivalence(c, c2, x, w)
    assert eq["equal"], eq


def test_grad_through_rewrite():
    f, (x, w) = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    g1x, g1w = jax.grad(lambda x, w: f(x, w).sum(), argnums=(0, 1))(x, w)
    g2x, g2w = jax.grad(lambda x, w: f2(x, w).sum(), argnums=(0, 1))(x, w)
    assert g1x.shape == g2x.shape and g1w.shape == g2w.shape
    # Each gradient is a dot over 520 float32 products, and the padded
    # operands have another shape, so the backend accumulates them in
    # another order: the plain and the rewritten gradient each lie 3-7e-6
    # from the float64 one (entries up to 8.6), and as far from each
    # other. Hold both to the float64 gradient within the error a sum of
    # n terms has, sqrt(n) * eps * the largest entry: a rewrite that
    # dropped or moved a column is off by 1e-2 and more.
    tx, tw = jax.grad(lambda x, w: f(x, w).sum(), argnums=(0, 1))(
        x.astype(jnp.float64), w.astype(jnp.float64))
    eps = float(jnp.finfo(jnp.float32).eps)
    for true, plain, rewritten in ((tx, g1x, g2x), (tw, g1w, g2w)):
        atol = 520 ** 0.5 * eps * float(jnp.abs(true).max())
        assert plain.dtype == rewritten.dtype == jnp.float32
        assert onp.allclose(plain, true, rtol=2e-5, atol=atol)
        assert onp.allclose(rewritten, true, rtol=2e-5, atol=atol)


def test_custom_vjp_rule_survives_rewrite():
    """The replay must re-bind custom_vjp calls (get_bind_params), not
    inline their bodies — a deliberately 'wrong' custom backward is the
    detector: plain AD through the inlined body would return 1s, the
    preserved rule returns 3s."""
    @jax.custom_vjp
    def marked(x):
        return x * 1.0

    def fwd(x):
        return marked(x), None

    def bwd(_, g):
        return (g * 3.0,)          # deliberately != the true gradient

    marked.defvjp(fwd, bwd)

    def f(x):
        # exact churn so a rewrite actually applies around the call
        y = x.astype(jnp.float32).astype(jnp.bfloat16)
        return marked(y.astype(jnp.float32)).sum()

    x = jnp.asarray(onp.ones((4, 4)), jnp.bfloat16)
    f2, rep = rewrite_callable(f, x, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied >= 1
    g = jax.grad(lambda x: f2(x).astype(jnp.float32))(x)
    assert bool((onp.asarray(g.astype(jnp.float32)) == 3.0).all()), \
        "custom_vjp backward was lost in the replay"


def test_rewritten_callable_rejects_other_shapes():
    f, (x, w) = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    bigger = jnp.concatenate([x, x], axis=0)
    with pytest.raises(TypeError, match="specialized"):
        f2(bigger, w)


def test_grouped_conv_is_refused():
    from jax import lax

    rng = onp.random.RandomState(4)
    x = jnp.asarray(rng.randn(1, 16, 8, 8), jnp.float32)
    w = jnp.asarray(rng.randn(16, 1, 3, 3), jnp.float32)  # depthwise

    def c(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
        return lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
            feature_group_count=16)

    c2, rep = rewrite_callable(c, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert c2 is c
    assert rep.n_applied == 0
    assert any("depthwise" in d.note or "group" in d.note
               for d in rep.refused), rep.render()


# ---------------------------------------------------------------------------
# J003 churn
# ---------------------------------------------------------------------------
def test_churn_exact_roundtrip_cancels_bitwise():
    def g(x):
        y = x.astype(jnp.float32)          # widen
        return (y.astype(jnp.bfloat16)      # narrow back: exact
                * jnp.bfloat16(2))

    x = jnp.asarray(onp.random.RandomState(0).randn(8, 128),
                    jnp.bfloat16)
    g2, rep = rewrite_callable(g, x, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    assert rep.applied[0].rule == "J003"
    eq = check_equivalence(g, g2, x, bitwise=True)
    assert eq["equal"], eq


def test_churn_lossy_roundtrip_is_kept():
    def h(x):
        # f32 -> bf16 -> f32 ROUNDS: cancelling would change numerics
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1

    x = jnp.asarray(onp.random.RandomState(0).randn(8, 128),
                    jnp.float32)
    h2, rep = rewrite_callable(h, x, model=TPU_MODEL,
                               mode_override="rewrite")
    assert h2 is h
    assert rep.n_applied == 0
    assert any(d.rule == "J003" and "lossy" in d.note
               for d in rep.refused)


def test_exactly_representable_table():
    yes = [("bfloat16", "float32"), ("float16", "float32"),
           ("float32", "float64"), ("int8", "int32"),
           ("uint8", "int32"), ("int16", "float32"),
           ("int32", "float64"), ("bool", "int8"),
           ("float32", "float32")]
    no = [("float32", "bfloat16"), ("float32", "float16"),
          ("float16", "bfloat16"), ("int32", "float32"),
          ("int32", "int16"), ("int8", "uint8"),
          ("float64", "float32")]
    for a, b in yes:
        assert _exactly_representable(a, b), (a, b)
    for a, b in no:
        assert not _exactly_representable(a, b), (a, b)


# ---------------------------------------------------------------------------
# gating: modes + the no-regression guard
# ---------------------------------------------------------------------------
def test_no_regression_guard_cpu_target():
    """A rewrite the cost model predicts as a loss is left untouched:
    J001 padding on a CPU target adds real FLOPs for no relayout win."""
    f, args = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, *args, model=CPU_MODEL,
                               mode_override="rewrite")
    assert f2 is f                       # untouched, not just unapplied
    assert rep.n_applied == 0
    d = next(d for d in rep.refused if d.rule == "J001")
    assert d.predicted_gain_s < 0        # a predicted LOSS, recorded
    assert "cpu target" in d.note


def test_advise_mode_plans_without_transform(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_OPT", raising=False)
    f, args = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, *args, model=TPU_MODEL)
    assert rep.mode == "advise"
    assert f2 is f
    assert rep.n_applied == 0
    assert any("advise" in d.note for d in rep.refused)


def test_off_mode_plans_nothing(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_OPT", "off")
    f, args = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, *args, model=TPU_MODEL)
    assert f2 is f
    assert rep.mode == "off"
    assert not rep.decisions()


def test_rewrite_env_mode_applies(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_OPT", "rewrite")
    f, args = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, *args, model=TPU_MODEL)
    assert f2 is not f
    assert rep.n_applied == 1


def test_rewritten_callable_zero_retraces():
    f, (x, w) = _misaligned_dot(jnp.float32)
    f2, rep = rewrite_callable(f, x, w, model=TPU_MODEL,
                               mode_override="rewrite")
    assert rep.n_applied == 1
    j = jax.jit(f2)
    for _ in range(4):
        out = j(x, w)
    jax.block_until_ready(out)
    assert j._cache_size() == 1          # one trace, stable executable


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_cost_model_monotonic_and_dtype_aware():
    m = TPU_MODEL

    def mm(n):
        x = jax.ShapeDtypeStruct((n, n), jnp.bfloat16)
        return m.estimate_callable(lambda a, b: a @ b, x, x)

    small, big = mm(256), mm(1024)
    assert big.t_total_s > small.t_total_s
    assert big.flops_padded == 2.0 * 1024 ** 3
    # dtype-aware bytes: f32 moves twice the bytes of bf16
    xb = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    xf = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    eb = m.estimate_callable(lambda a, b: a @ b, xb, xb)
    ef = m.estimate_callable(lambda a, b: a @ b, xf, xf)
    assert abs(ef.bytes_naive / eb.bytes_naive - 2.0) < 1e-6
    # launch overhead amortizes with steps_per_launch
    e1 = m.estimate_callable(lambda a, b: a @ b, xb, xb,
                             steps_per_launch=1)
    e16 = m.estimate_callable(lambda a, b: a @ b, xb, xb,
                              steps_per_launch=16)
    assert e16.t_launch_s == pytest.approx(e1.t_launch_s / 16)
    # padded-tile accounting: misaligned K pads 130 -> 256
    xm = jax.ShapeDtypeStruct((512, 130), jnp.bfloat16)
    wm = jax.ShapeDtypeStruct((130, 512), jnp.bfloat16)
    em = m.estimate_callable(lambda a, b: a @ b, xm, wm)
    assert em.flops_padded == 2.0 * 512 * 256 * 512
    assert em.tile_waste == pytest.approx(1 - 130 / 256)


def test_spearman_basics():
    assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert abs(spearman([1, 2, 3, 4], [2, 1, 4, 3])) < 1.0


def test_cost_model_rank_correlation_banked_corpus():
    """The acceptance gate: predicted step time must rank the banked
    TPU corpus (>= 10 re-traced workloads) with Spearman >= 0.8 —
    offline, tracing only, no TPU. Also: calibration must not degrade
    the rank below the gate."""
    from mxnet_tpu.analysis.opt import calibration as cal

    samples = cal.corpus(kinds=("infer",))
    assert len(samples) >= 10, \
        f"banked infer corpus shrank: {len(samples)} rows"
    model = CostModel()                      # v5e defaults
    table = cal.calibration_table(model, samples)
    rho = table[0]["spearman_all"]
    assert rho >= 0.8, f"rank correlation degraded: {rho}\n" + \
        "\n".join(f"{r['name']}: pred {r['predicted_step_ms']} ms vs "
                  f"banked {r['observed_step_ms']} ms" for r in table)
    fitted, diag = model.calibrate([s.as_tuple() for s in samples])
    assert diag["after"]["spearman"] >= 0.8
    assert diag["after"]["msle"] <= diag["before"]["msle"] + 1e-9


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------
def _mlp_builder_factory():
    rng = onp.random.RandomState(0)
    W = jnp.asarray(rng.randn(64, 64) * 0.1, jnp.float32)
    x0 = jnp.asarray(rng.randn(8, 64), jnp.float32)

    def builder(steps_per_launch=1):
        def one(x):
            return jnp.tanh(x @ W)
        if steps_per_launch == 1:
            return jax.jit(one), (x0,)

        def chain(x):
            def body(c, _):
                return one(c), ()
            y, _ = jax.lax.scan(body, x, None,
                                length=steps_per_launch)
            return y
        return jax.jit(chain), (x0,)

    return builder


def test_autotune_deterministic_with_injected_timer(tmp_path):
    """Same builder + same fake clock => identical verdict (knobs AND
    fingerprint key), run twice."""
    builder = _mlp_builder_factory()

    def make_timer():
        t = [0.0]

        def timer():
            t[0] += 0.001
            return t[0]
        return timer

    kw = dict(label="det", space={"steps_per_launch": (1, 4, 16)},
              model=CPU_MODEL, probe_top_k=2, probe_reps=2,
              save=False)
    cfg1 = opt.autotune(builder, timer=make_timer(), **kw)
    cfg2 = opt.autotune(builder, timer=make_timer(), **kw)
    assert cfg1.knobs == cfg2.knobs
    assert cfg1.key == cfg2.key
    assert cfg1.probes == cfg2.probes


def test_autotune_probes_include_default_floor(tmp_path):
    """The all-defaults combo is always measured, so the tuner cannot
    crown an unmeasured exotic over a faster default."""
    builder = _mlp_builder_factory()
    cfg = opt.autotune(builder, label="floor",
                       space={"steps_per_launch": (1, 16, 32)},
                       model=CPU_MODEL, probe_top_k=1, probe_reps=1,
                       save=False)
    assert any(r["knobs"] == {"steps_per_launch": 1}
               for r in cfg.candidates)


def test_tuned_config_roundtrip_and_lookup(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_OPT_DIR", str(tmp_path))
    builder = _mlp_builder_factory()
    cfg = opt.autotune(builder, label="rt",
                       space={"steps_per_launch": (1, 4)},
                       model=CPU_MODEL, probe_top_k=1, probe_reps=1)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(files) == 1
    loaded = opt.load_tuned(os.path.join(tmp_path, files[0]))
    assert loaded.key == cfg.key
    assert loaded.knobs == cfg.knobs
    # keyed lookup resolves
    fn, args = builder(1)
    got = opt.lookup("rt", fn, args, space={"steps_per_launch": (1, 4)})
    assert got is not None and got.key == cfg.key


def test_fingerprint_invalidation_on_knob_and_jaxlib_flip(
        tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_OPT_DIR", str(tmp_path))
    builder = _mlp_builder_factory()
    cfg = opt.autotune(builder, label="inv",
                       space={"steps_per_launch": (1, 4)},
                       model=CPU_MODEL, probe_top_k=1, probe_reps=1)
    fn, args = builder(1)
    space = {"steps_per_launch": (1, 4)}
    assert opt.lookup("inv", fn, args, space=space) is not None
    # an A002 env-knob flip invalidates (stem_s2d is in the corpus)
    monkeypatch.setenv("MXNET_TPU_STEM_S2D", "0")
    assert opt.lookup("inv", fn, args, space=space) is None
    monkeypatch.delenv("MXNET_TPU_STEM_S2D")
    assert opt.lookup("inv", fn, args, space=space) is not None
    # a jaxlib upgrade invalidates without any knob changing
    from mxnet_tpu.aot import cache as aot_cache

    monkeypatch.setattr(aot_cache, "jaxlib_version",
                        lambda: "99.99.99-fake")
    assert not cfg.is_current()
    assert opt.lookup("inv", fn, args, space=space) is None


# ---------------------------------------------------------------------------
# consumption: Trainer + InferenceEngine
# ---------------------------------------------------------------------------
def _manual_config(knobs, stale=False):
    return opt.TunedConfig(
        label="manual", key="k" * 64, knobs=knobs,
        jaxlib_version="0.0.0-stale" if stale else "")


def test_engine_consumes_tuned_buckets():
    from mxnet_tpu.serving import InferenceEngine

    cfg = _manual_config({"bucket_sizes": [2, 4], "max_delay_ms": 1.0})
    eng = InferenceEngine(lambda x: x * 2, jit=False, tuned=cfg)
    try:
        assert eng.tuned is cfg
        assert eng.max_batch_size == 4
        assert eng.max_delay_ms == 1.0
        assert eng._bucket_ladder == (2, 4)
        out = eng.infer(onp.ones((1, 3), "float32"))
        assert out.shape == (1, 3)
        assert eng.stats()["tuned"]["label"] == "manual"
    finally:
        eng.close()


def test_engine_ignores_stale_tuned():
    from mxnet_tpu.serving import InferenceEngine

    cfg = _manual_config({"bucket_sizes": [2, 4]}, stale=True)
    with pytest.warns(RuntimeWarning, match="stale"):
        eng = InferenceEngine(lambda x: x, jit=False, tuned=cfg)
    try:
        assert eng.tuned is None
        assert eng._bucket_ladder is None      # pow2 default kept
    finally:
        eng.close()


def test_trainer_consumes_tuned():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    cfg = _manual_config({"steps_per_launch": 8})
    net = gluon.nn.Dense(4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, tuned=cfg)
    assert tr.tuned is cfg
    assert tr.tuned_steps_per_launch == 8
    x = mx.np.array(onp.ones((2, 8), "float32"))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    tr.step(2)
    # the tuned key is folded into the fused-update AOT fingerprint
    assert tr._jit_step._static == (("tuned", cfg.key),)
    # stale config: warned and dropped
    with pytest.warns(RuntimeWarning, match="stale"):
        tr2 = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            tuned=_manual_config({}, stale=True))
    assert tr2.tuned is None
    assert tr2.tuned_steps_per_launch == 1


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_opt_telemetry_gauges():
    from mxnet_tpu.telemetry import get_registry

    f, args = _misaligned_dot(jnp.float32)
    rewrite_callable(f, *args, model=TPU_MODEL,
                     mode_override="rewrite")
    opt.autotune(_mlp_builder_factory(), label="telemetry",
                 space={"steps_per_launch": (1, 4)}, model=CPU_MODEL,
                 probe_top_k=1, probe_reps=1, save=False)
    opt.record_prediction("telemetry", 0.001, 0.002)
    snap = get_registry().snapshot()
    names = set(snap.get("metrics", snap))
    for want in ("opt_rewrites_applied_total", "opt_tune_probe_ms",
                 "opt_tune_best_ms", "opt_tune_probes_total",
                 "opt_tune_spend_s", "opt_predicted_step_ms",
                 "opt_observed_step_ms"):
        assert want in names, f"{want} missing from registry: {names}"


# ---------------------------------------------------------------------------
# the tier-1 bench smoke
# ---------------------------------------------------------------------------
def test_opt_bench_quick():
    """opt_bench --quick end to end: oracle passes, zero retraces, the
    three stages + rewrite report land in the artifact. (The >=1.15x
    acceptance is asserted on the banked non-quick artifact, where the
    timed windows are long enough to be stable.)"""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "opt_bench.py"),
         "--quick", "--no-bank"],
        capture_output=True, text=True, timeout=420, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(proc.stdout)
    assert rec["quick"] is True
    assert rec["acceptance"]["oracle_pass"] is True
    assert rec["acceptance"]["zero_retraces"] is True
    assert rec["rewrites"]["applied"], "no rewrite applied in the smoke"
    # the CPU no-regression guard fired on the J001 candidates
    assert any(r["rule"] == "J001" and r["predicted_gain_us"] < 0
               for r in rec["rewrites"]["refused"])
    stages = rec["stages"]
    assert stages["default_steps_s"] > 0
    assert stages["tuned_steps_s"] > 0
    assert "J001" in rec["workload"]["lint_rules_before"]
    assert "J003" in rec["workload"]["lint_rules_before"]


def test_banked_opt_artifact_acceptance():
    """The banked results_opt_cpu.json must carry the ISSUE-9
    acceptance: tuned >= 1.15x default, oracle pass, zero retraces,
    Spearman >= 0.8 on >= 10 corpus rows."""
    path = os.path.join(ROOT, "benchmark", "results_opt_cpu.json")
    assert os.path.exists(path), "results_opt_cpu.json not banked"
    with open(path) as f:
        rec = json.load(f)["record"]
    acc = rec["acceptance"]
    assert rec["stages"]["speedup_tuned"] >= 1.15
    assert acc["oracle_pass"] is True
    assert acc["zero_retraces"] is True
    assert rec["calibration"]["n_rows"] >= 10
    assert rec["calibration"]["spearman"] >= 0.8
