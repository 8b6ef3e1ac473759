"""A hybridized block's recorded call runs its forward once (PR 35).

Under ``autograd.record()`` the cached graph runs the program that
returns the outputs *and* the pullback's residuals, and the tape node's
pullback is the compiled backward over them; an unrecorded call runs the
plain forward and compiles nothing else. What is held here: the gradients
are those of ``jax.grad`` over ``functionalize``; the backward program
holds the transposes only; the residuals live exactly as long as the
tape node; forward state is written once a call; the span
``autograd.backward`` says how many bytes the forwards handed over.
"""
import gc
import re
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops.dispatch import autograd_state
from mxnet_tpu.telemetry import tracing

KEY = jax.random.PRNGKey(35)


class _TwoHeads(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.body = nn.Dense(12, activation="tanh")
        self.a = nn.Dense(3, use_bias=False)
        self.b = nn.Dense(5, activation="sigmoid")

    def forward(self, x):
        h = self.body(x)
        a = self.a(h)       # a matmul's result: an output and a residual
        return a, self.b(h) * mx.np.tanh(a).sum()


def _seq(*layers):
    net = nn.HybridSequential()
    net.add(*layers)
    return net


def _gpt():
    from mxnet_tpu.gluon.model_zoo import bert

    return bert.gpt_like(vocab_size=61, units=32, hidden_size=64,
                         num_layers=2, num_heads=4, max_length=24,
                         dropout=0.0)


NETS = {
    "mlp": lambda: _seq(nn.Dense(16, activation="relu"), nn.Dense(4)),
    "batchnorm": lambda: _seq(nn.Dense(16), nn.BatchNorm(), nn.Dense(4)),
    "dropout": lambda: _seq(nn.Dense(16, activation="relu"),
                            nn.Dropout(0.5), nn.Dense(4)),
    "two_outputs": _TwoHeads,
    "gpt_like": _gpt,
}


def _input(name, rng):
    if name == "gpt_like":
        return mx.np.array(rng.randint(0, 61, (2, 16)).astype("int32"))
    return mx.np.array(rng.normal(0, 1, (6, 8)).astype("float32"))


def _build(name, seed=0):
    rng = onp.random.RandomState(seed)
    net = NETS[name]()
    net.initialize()
    x = _input(name, rng)
    with autograd.pause():
        net(x)                       # deferred shapes, before hybridize
    net.hybridize()
    return net, x


def _n_outputs(net, x):
    with autograd.pause(train_mode=True):
        return len(jax.tree_util.tree_leaves(net(x)))


def _output_weights(net, x, seed):
    with autograd.pause(train_mode=True):
        outs = jax.tree_util.tree_leaves(net(x))
    return _weights(outs, onp.random.RandomState(seed))


def _weights(outs, rng):
    return [jnp.asarray(rng.normal(0, 1, o.shape).astype("float32"))
            for o in outs]


def _loss(outs, ws):
    outs = outs if isinstance(outs, (tuple, list)) else (outs,)
    total = 0.0
    for o, w in zip(outs, ws):
        total = total + (o * w).sum()
    return total


def _record_step(net, x, ws):
    with autograd.record():
        outs = net(x)
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        loss = _loss(outs, [mx.np.array(onp.asarray(w)) for w in ws])
    node = autograd_state.tape.nodes[0]
    return loss, node


def _grads(net):
    return {n: onp.asarray(p.grad().asnumpy())
            for n, p in net.collect_params().items()
            if p.grad_req != "null"}


@pytest.fixture
def fixed_key(monkeypatch):
    """The cached call draws its key from the global stream; pin it so
    that ``functionalize`` can be given the same one."""
    monkeypatch.setattr(mx.numpy.random, "new_key", lambda: KEY)


# --- (a) the gradients are jax.grad's over the functionalized net ----------
@pytest.mark.parametrize("name", sorted(NETS))
def test_recorded_gradients_equal_jax_grad(name, fixed_key):
    net, x = _build(name)
    fn, params = net.functionalize(x, training=True)
    floats = name != "gpt_like"
    out_shapes = jax.eval_shape(lambda p, v: fn(p, v, key=KEY)[0],
                                params, x._data)
    ws = _weights(jax.tree_util.tree_leaves(out_shapes),
                  onp.random.RandomState(1))

    def loss(p, v):
        return _loss(fn(p, v, key=KEY)[0], ws)

    want_p, want_x = jax.grad(loss, argnums=(0, 1), allow_int=True)(
        params, x._data)

    if floats:
        x.attach_grad()
    _record_step(net, x, ws)[0].backward()
    got = _grads(net)
    assert got
    for n, g in got.items():
        onp.testing.assert_allclose(g, onp.asarray(want_p[n]),
                                    rtol=2e-4, atol=2e-5, err_msg=n)
    if floats:
        onp.testing.assert_allclose(x.grad.asnumpy(), onp.asarray(want_x),
                                    rtol=2e-4, atol=2e-5)


# --- (b) the backward program holds the transposes only --------------------
def _programs(net, x):
    """The cached graph of ``net`` for ``x`` in training mode, its
    programs' arguments, and the avals of what the recorded forward
    returns."""
    plist = net._ensure_params_ready((x,))
    flat, treedef = jax.tree_util.tree_flatten((x,))
    cg = net._build_cache((x,), flat, treedef, True, plist)
    vals = [p.data()._data for _, p in cg.param_list] + [x._data, KEY]
    outs, res = jax.eval_shape(cg.fwd_res_fn, *vals)
    return cg, vals, (res, vals, outs, tuple(outs))


def _matmuls(text):
    return len(re.findall(r"\b(?:dot|convolution)\(", text))


@pytest.mark.parametrize("name", ["mlp", "two_outputs", "gpt_like"])
def test_backward_program_holds_no_forward(name):
    net, x = _build(name)
    cg, vals, bwd_args = _programs(net, x)
    plain = _matmuls(cg.fwd_fn.lower(*vals).compile().as_text())
    fwd = _matmuls(cg.fwd_res_fn.lower(*vals).compile().as_text())
    bwd = _matmuls(cg.bwd_fn.lower(*bwd_args).compile().as_text())
    assert fwd == plain > 0          # the recorded forward computes once
    if name == "two_outputs":
        # the first output is also what tanh's pullback is rebuilt from:
        # named, not returned a second time
        assert ("out", 0) in cg.res_plan["src"]
    if name == "gpt_like":
        # at most two transposes a matmul (the compiler merges a few);
        # never the forward's matmuls a second time
        assert fwd < bwd <= 2 * fwd
    else:
        assert bwd == 2 * fwd


def _pallas_paths(jaxpr, path=()):
    for e in jaxpr.eqns:
        here = path
        if e.primitive.name in ("jit", "pjit"):
            here = path + (e.params["name"],)
        if e.primitive.name == "pallas_call":
            yield path
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _pallas_paths(sub, here)


def test_backward_program_runs_no_flash_forward(monkeypatch):
    """With the kernels selected (steered: nothing is lowered here), the
    recorded forward holds the flash forward once a layer and the
    backward program's kernels are the flash backward's alone."""
    from mxnet_tpu.gluon.model_zoo import bert

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = bert.gpt_like(vocab_size=640, units=128, hidden_size=256,
                        num_layers=2, num_heads=2, max_length=256,
                        dropout=0.0)
    net.initialize()
    net.hybridize()
    x = mx.np.array(onp.zeros((2, 256), onp.int32))
    cg, vals, bwd_args = _programs(net, x)
    fwd = list(_pallas_paths(jax.make_jaxpr(cg.fwd_res_fn)(*vals).jaxpr))
    bwd = list(_pallas_paths(jax.make_jaxpr(cg.bwd_fn)(*bwd_args).jaxpr))
    assert sum("_flash_forward" in p for p in fwd) == 2
    assert bwd and all("_flash_bwd_pallas" in p for p in bwd), bwd
    # and no weight comes back from the forward: each is named as the
    # program's own input
    shapes = {tuple(v.shape) for v in vals[:-2] if v.ndim == 2}
    res = bwd_args[0]
    assert not [r.shape for r in res
                if tuple(r.shape) in shapes or tuple(r.shape[::-1]) in shapes]
    named = {n for k, n in cg.res_plan["src"] if k == "in"}
    assert len(named) >= 2 * 4 + 1


def _avals(*shapes):
    return [jax.core.ShapedArray(s, jnp.float32) for s in shapes]


_MATMUL = {"dimension_numbers": (((2,), (1,)), ((), ()))}   # x @ W.T
POLICY_CASES = {
    # 8,192 tokens x 768 into 2,304: larger than both operands: rebuilt
    "dot_widening": ("dot_general", [(8, 1024, 768), (2304, 768)], _MATMUL,
                     False),
    "dot_square": ("dot_general", [(8, 1024, 768), (768, 768)], _MATMUL,
                   True),
    "dot_narrowing": ("dot_general", [(8, 1024, 3072), (768, 3072)], _MATMUL,
                      True),
    "dot_batched_scores": (
        "dot_general", [(8, 12, 1024, 64), (8, 12, 1024, 64)],
        {"dimension_numbers": (((3,), (3,)), ((0, 1), (0, 1)))}, False),
    "dot_toy": ("dot_general", [(2, 16, 32), (96, 32)], _MATMUL, True),
    "sum_of_two_arrays": ("add", [(8, 1024, 768), (8, 1024, 768)], {}, True),
    "sum_with_a_scalar": ("add", [(8, 1024, 3072), ()], {}, False),
    "sum_of_scalars": ("add", [(), ()], {}, False),
    "cotangent_sum": ("add_any", [(8, 768), (8, 768)], {}, True),
    "convolution": ("conv_general_dilated", [(8, 3, 32, 32), (16, 3, 3, 3)],
                    {}, True),
    "kernel": ("pallas_call", [(8192, 768)], {}, True),
    "activation": ("erf", [(8, 1024, 3072)], {}, False),
    "transpose": ("transpose", [(2304, 768)], {}, False),
    "broadcast": ("broadcast_in_dim", [(768,)], {}, False),
    "mask_bits": ("random_bits", [(2,)], {}, False),
}


@pytest.mark.parametrize("case", sorted(POLICY_CASES))
def test_residual_policy(case):
    """What the recorded forward keeps for its backward, by primitive."""
    from types import SimpleNamespace

    from mxnet_tpu.gluon.block import _residual_policy

    name, shapes, params, kept = POLICY_CASES[case]
    assert _residual_policy(SimpleNamespace(name=name), *_avals(*shapes),
                            **params) is kept


def test_backward_rebuilds_a_matmul_larger_than_its_operands():
    """At many rows a widening projection's result is rebuilt from its
    saved input: one matmul of the forward comes back, the other does
    not, and nothing wider than the input is handed over."""
    net = _seq(nn.Dense(64, activation="relu", flatten=False),
               nn.Dense(8, flatten=False))
    net.initialize()
    x = mx.np.array(onp.random.RandomState(0).normal(
        0, 1, (4, 256, 8)).astype("float32"))
    with autograd.pause():
        net(x)
    net.hybridize()
    cg, vals, bwd_args = _programs(net, x)
    fwd = _matmuls(cg.fwd_res_fn.lower(*vals).compile().as_text())
    bwd = _matmuls(cg.bwd_fn.lower(*bwd_args).compile().as_text())
    assert (fwd, bwd) == (2, 2 * 2 + 1)
    assert not [r.shape for r in bwd_args[0] if r.shape[-1] == 64]


# --- (c) the residuals live as long as the node ----------------------------
def _residuals(node):
    cells = {n: c.cell_contents for n, c in zip(
        node.vjp_fn.__code__.co_freevars, node.vjp_fn.__closure__)}
    return cells["res"]


@pytest.mark.parametrize("name", ["mlp", "batchnorm", "gpt_like"])
def test_retain_graph_gives_equal_gradients_twice(name, fixed_key):
    net, x = _build(name)
    ws = _output_weights(net, x, 2)
    loss, node = _record_step(net, x, ws)
    loss.backward(retain_graph=True)
    first = _grads(net)
    assert node.vjp_fn is not None and _residuals(node)
    loss.backward()
    second = _grads(net)
    assert first.keys() == second.keys() and first
    for n in first:
        onp.testing.assert_array_equal(first[n], second[n], err_msg=n)
    assert node.vjp_fn is None


@pytest.mark.parametrize("name", ["mlp", "gpt_like"])
def test_backward_releases_the_residuals(name):
    net, x = _build(name)
    ws = _output_weights(net, x, 3)
    loss, node = _record_step(net, x, ws)
    refs = [weakref.ref(r) for r in _residuals(node)]
    assert refs and node.residual_bytes == sum(r().nbytes for r in refs)
    cg = next(iter(net._cached_graphs.values()))
    loss.backward()
    assert node.vjp_fn is None
    del loss
    gc.collect()
    assert not [r for r in refs if r() is not None]
    # and the graph keeps no array: its plan is a treedef and positions
    assert not [v for v in jax.tree_util.tree_leaves(
        {k: v for k, v in cg.res_plan.items() if k != "treedef"})
        if isinstance(v, jax.Array)]


# --- (d) recording decides which program runs ------------------------------
_COMPILES = [0]     # one listener for the module: jax keeps it for good


def _on_compile(event, duration, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile)


@pytest.mark.parametrize("name", ["mlp", "two_outputs"])
def test_unrecorded_call_runs_the_plain_program(name):
    net, x = _build(name)
    x.attach_grad()
    with autograd.pause():
        net(x)                                   # predict mode, warm
    n_out = _n_outputs(net, x)                   # training mode, paused
    before = _COMPILES[0]
    with autograd.pause(train_mode=True):
        out = net(x)
    assert _COMPILES[0] == before
    assert len(jax.tree_util.tree_leaves(out)) == n_out
    graphs = list(net._cached_graphs.values())
    assert len(graphs) == 2
    for cg in graphs:                            # nothing was recorded:
        assert cg.fwd_fn._cache_size() == 1      # the plain program only
        assert cg.fwd_res_fn._cache_size() == 0
        assert cg.bwd_fn._cache_size() == 0 and not cg.res_plan
    with autograd.record():                      # first recorded call:
        loss = _loss(net(x), [1.0] * n_out)      # built on first use
    loss.backward()
    assert _COMPILES[0] > before
    trained = [cg for cg in graphs if cg.fwd_res_fn._cache_size()]
    assert len(trained) == 1 and trained[0].bwd_fn._cache_size() == 1


def test_a_net_that_only_trains_compiles_no_plain_forward():
    net, x = _build("mlp")
    for _ in range(2):
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
    (cg,) = net._cached_graphs.values()
    assert cg.fwd_fn._cache_size() == 0
    assert cg.fwd_res_fn._cache_size() == 1 and cg.bwd_fn._cache_size() == 1


def test_grad_req_null_net_is_not_recorded():
    net = NETS["mlp"]()
    for p in net.collect_params().values():
        p.grad_req = "null"              # as a net that only serves is made
    net.initialize()
    net.hybridize()
    x = _input("mlp", onp.random.RandomState(0))
    with autograd.record():
        net(x)
    (cg,) = net._cached_graphs.values()
    assert cg.fwd_res_fn._cache_size() == 0 and not autograd_state.tape.nodes


# --- (e) forward state is written once a recorded call ---------------------
@pytest.mark.parametrize("calls", [1, 3])
def test_batchnorm_statistics_written_once_a_call(calls):
    net, x = _build("batchnorm")
    twin = NETS["batchnorm"]()
    twin.initialize()
    with autograd.pause():
        twin(x)
    for (n, p), (_, q) in zip(sorted(net.collect_params().items()),
                              sorted(twin.collect_params().items())):
        q.set_data(p.data())
    for _ in range(calls):
        for m in (net, twin):                    # twin: eager, op by op
            with autograd.record():
                loss = m(x).sum()
            loss.backward()
    stats = [n for n in net.collect_params() if "running" in n]
    assert len(stats) == 2
    for n in stats:
        got = net.collect_params()[n].data().asnumpy()
        want = twin.collect_params()[n].data().asnumpy()
        onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert not onp.allclose(got, 0.0 if "mean" in n else 1.0)


# --- (f) the span says what the forwards handed over -----------------------
@pytest.mark.parametrize("hybrid", [True, False], ids=["hybrid", "eager"])
def test_backward_span_carries_residual_bytes(hybrid):
    net, x = _build("mlp")
    if not hybrid:
        net.hybridize(False)
    t0 = time.perf_counter()
    with autograd.record():
        loss = net(x).sum()
    node_bytes = sum(n.residual_bytes for n in autograd_state.tape.nodes)
    loss.backward()
    spans = tracing.rows(t0, time.perf_counter(), "autograd.backward")
    assert len(spans) == 1
    args = spans[0][3]
    assert args["residual_bytes"] == node_bytes
    if hybrid:
        # two matmuls' outputs of (6, 16) and (6, 4) float32 at least
        assert args["residual_bytes"] >= 6 * 16 * 4
    else:
        assert args["residual_bytes"] == 0
