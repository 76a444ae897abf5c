"""Fused whole-train-step compilation (Trainer.compile_step / TrainLoop).

Covers the PR-1 acceptance bar: numerics parity with the eager
record/backward/step loop for SGD-momentum and Adam over >=3 steps,
exactly one compile per input-shape bucket across repeated steps and lr
changes, donation writeback keeping Parameter handles stable, the
transparent eager fallback, and the split (host-allreduce) mode for dist
stores.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon import loss as gloss


def _build(seed=3, with_bn=True):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    if with_bn:
        # bias-free: a bias feeding BN has a ~0 gradient (mean
        # subtraction cancels shift), and Adam's sign-normalizing update
        # amplifies sub-1e-8 autodiff reduction-order noise to ~lr —
        # that would test float noise, not the fused step
        net.add(nn.Dense(8, in_units=4, activation="relu",
                         use_bias=False))
        net.add(nn.BatchNorm(in_channels=8))
    else:
        net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(3, in_units=8))
    net.initialize()
    return net


def _batch(bs=6, seed=0):
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.randn(bs, 4).astype("float32"))
    y = nd.array(rng.randint(0, 3, size=(bs,)).astype("int32"))
    return x, y


def _assert_params_close(net_a, net_b, rtol=1e-5, atol=1e-6):
    for (k, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                sorted(net_b.collect_params().items())):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=rtol, atol=atol, err_msg=k)


def _run_eager(net, opt, opt_kwargs, x, y, steps, lr_change=None):
    trainer = Trainer(net.collect_params(), opt, dict(opt_kwargs))
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    for i in range(steps):
        if lr_change and i == lr_change[0]:
            trainer.learning_rate = lr_change[1]
        with autograd.record():
            l = loss_blk(net(x), y)
        l.backward()
        trainer.step(x.shape[0])
    return trainer


def _run_fused(net, opt, opt_kwargs, x, y, steps, lr_change=None,
               kvstore="device"):
    trainer = Trainer(net.collect_params(), opt, dict(opt_kwargs),
                      kvstore=kvstore)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    for i in range(steps):
        if lr_change and i == lr_change[0]:
            trainer.learning_rate = lr_change[1]
        step(x, y)
    return trainer, step


@pytest.mark.parametrize("opt,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_compile_step_parity_vs_eager(opt, kwargs):
    """Weights (incl. BatchNorm running stats) after >=3 fused steps —
    with an lr change mid-run — match the eager tape loop."""
    x, y = _batch()
    net_e = _build()
    _run_eager(net_e, opt, kwargs, x, y, steps=4, lr_change=(2, 0.02))
    net_f = _build()
    _, step = _run_fused(net_f, opt, kwargs, x, y, steps=4,
                         lr_change=(2, 0.02))
    assert step.mode == "fused"
    _assert_params_close(net_e, net_f)


def test_compile_step_parity_with_clip_and_wd():
    x, y = _batch()
    kwargs = {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3,
              "clip_gradient": 0.5}
    net_e = _build(with_bn=False)
    _run_eager(net_e, "sgd", kwargs, x, y, steps=3)
    net_f = _build(with_bn=False)
    _, step = _run_fused(net_f, "sgd", kwargs, x, y, steps=3)
    assert step.mode == "fused"
    _assert_params_close(net_e, net_f)


def test_compile_step_retrace_policy():
    """Exactly ONE compile per input-shape bucket: repeated steps, lr
    mutation, and per-call batch_size changes reuse the program; only a
    genuinely new shape bucket compiles a second one."""
    net = _build(with_bn=False)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    x, y = _batch(6)
    for lr in (0.1, 0.05, 0.2):
        trainer.learning_rate = lr
        step(x, y)
    assert step.n_traces == 1, "lr changes must not retrace"
    step(x, y, batch_size=12)   # rescale is traced, not static
    assert step.n_traces == 1
    x2, y2 = _batch(3)
    step(x2, y2)                # new shape bucket
    assert step.n_traces == 2
    step(x, y)                  # back to the first bucket: cached
    assert step.n_traces == 2
    assert len(step._trace_signatures) == 2


def test_compile_step_writeback_keeps_handles():
    """Donation contract: results are written back INTO the same
    Parameter NDArray handles — references users hold from .data() see
    the updated weights."""
    net = _build(with_bn=False)
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    x, y = _batch()
    first = list(net.collect_params().values())[0]
    handle = first.data()
    before = handle.asnumpy().copy()
    step(x, y)
    assert first.data() is handle, "handle must stay stable"
    assert not onp.allclose(handle.asnumpy(), before), \
        "held handle must observe the update"


def test_compile_step_eager_fallback_transparent():
    """A loss_fn that concretizes on host (asnumpy inside) cannot trace;
    the step must fall back to the eager tape path with the same
    numerics, not raise."""
    x, y = _batch()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()

    net_f = _build(with_bn=False)
    trainer = Trainer(net_f.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})

    def hostile(a, b):
        out = net_f(a)
        _ = float(out.asnumpy().sum())   # breaks the trace
        return loss_blk(out, b)

    step = trainer.compile_step(hostile)
    for _ in range(2):
        step(x, y)
    assert step.mode == "eager"

    net_e = _build(with_bn=False)
    _run_eager(net_e, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
               x, y, steps=2)
    _assert_params_close(net_e, net_f)


def test_compile_step_fallback_rolls_back_update_counts():
    """A failed first trace must not leave the optimizer's update counts
    advanced — Adam's bias correction in the eager fallback has to see
    t=1 on the first real step."""
    x, y = _batch()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    net_f = _build(with_bn=False)
    trainer = Trainer(net_f.collect_params(), "adam",
                      {"learning_rate": 1e-2})

    def hostile(a, b):
        out = net_f(a)
        _ = float(out.asnumpy().sum())
        return loss_blk(out, b)

    step = trainer.compile_step(hostile)
    for _ in range(3):
        step(x, y)
    assert step.mode == "eager"
    assert trainer._optimizer.num_update == 3

    net_e = _build(with_bn=False)
    _run_eager(net_e, "adam", {"learning_rate": 1e-2}, x, y, steps=3)
    _assert_params_close(net_e, net_f)


def test_compile_step_first_call_lowering_error_raises():
    """Only an UNTRACEABLE loss demotes. A loss that traces but cannot
    lower or compile — here a Pallas kernel asked to compile for real on
    a backend that only interprets, the shape of a Mosaic rejection on
    the chip — raises on the first call: demoting would run (and time)
    per-op eager dispatch under the fused step's name."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops.kernels import norm as knorm
    x, y = _batch()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    net = _build(with_bn=False)
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})

    def unlowerable(a, b):
        out = net(a)._data
        ones = jnp.ones(out.shape[-1], out.dtype)
        out = knorm.layer_norm(out, ones, 0 * ones, interpret=False)
        return loss_blk(NDArray(out), b)

    step = trainer.compile_step(unlowerable)
    with pytest.raises(ValueError, match="interpret mode"):
        step(x, y)
    assert step.mode == "fused" and step._steps_done == 0


def test_compile_step_sparse_grad_falls_back():
    """Embedding with sparse_grad takes the lazy row path — compile_step
    must route to the eager loop, and training must still work."""
    mx.random.seed(5)
    net = nn.Sequential()
    net.add(nn.Embedding(16, 4, sparse_grad=True))
    net.initialize()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.5})

    def loss_fn(tok):
        return (net(tok) ** 2).mean()

    step = trainer.compile_step(loss_fn)
    tok = nd.array(onp.array([1, 3, 1], "int32"))
    before = net._children["0"].weight.data().asnumpy().copy()
    step(tok, batch_size=3)
    assert step.mode == "eager"
    after = net._children["0"].weight.data().asnumpy()
    assert not onp.allclose(after[1], before[1])
    onp.testing.assert_allclose(after[2], before[2])  # untouched row


def test_compile_step_split_mode_host_allreduce():
    """Dist stores (num_workers>1; forced here via _force_fuse) cannot
    reduce inside the program: grads route through the kvstore's
    bucketed pushpull_list between the gradient and update programs —
    numerics must still match the plain fused/eager path."""
    from mxnet_tpu.kvstore.kvstore import KVStoreDist
    x, y = _batch()
    kwargs = {"learning_rate": 0.1, "momentum": 0.9}

    kv = KVStoreDist("dist_sync")
    kv._force_fuse = True
    assert not kv.in_program_reduce
    net_s = _build()
    trainer, step = None, None
    trainer = Trainer(net_s.collect_params(), "sgd", dict(kwargs),
                      kvstore=kv)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net_s(a), b))
    for _ in range(3):
        step(x, y)
    assert step.mode == "fused"
    assert kv.stats["collectives"] == 0  # single process: identity reduce

    net_e = _build()
    _run_eager(net_e, "sgd", kwargs, x, y, steps=3)
    _assert_params_close(net_e, net_s)


def test_compile_step_save_load_states_interop():
    """The fused step drives the SAME Updater state dict the eager path
    uses: save_states after fused steps restores into an eager trainer."""
    x, y = _batch()
    net = _build(with_bn=False)
    trainer, step = _run_fused(net, "adam", {"learning_rate": 1e-2},
                               x, y, steps=3)
    assert step.mode == "fused"
    assert len(trainer._updater.states) == len(trainer._params)
    import tempfile
    import os as _os
    fd, fname = tempfile.mkstemp()
    _os.close(fd)
    try:
        trainer.save_states(fname)
        trainer2 = Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-2})
        trainer2.load_states(fname)
        assert len(trainer2._updater.states) == len(trainer._updater.states)
        assert trainer2._optimizer.num_update == \
            trainer._optimizer.num_update
    finally:
        _os.unlink(fname)


def test_train_loop_convergence_and_aot():
    """TrainLoop end-to-end: AOT compile reports the program, repeated
    steps reuse ONE compiled program, and the loss actually goes down."""
    rng = onp.random.RandomState(0)
    w_true = rng.randn(4, 3).astype("float32")
    xs = rng.randn(64, 4).astype("float32")
    ys = (xs @ w_true).argmax(axis=1).astype("int32")
    x, y = nd.array(xs), nd.array(ys)

    net = _build(with_bn=False)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.5, "momentum": 0.9})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss())
    loop.compiled_step.aot_compile(x, y)
    l0 = float(loop.step(x, y).asnumpy().mean())
    for _ in range(30):
        l = loop.step(x, y)
    l1 = float(l.asnumpy().mean())
    assert loop.compiled_step.n_traces == 1
    assert l1 < l0 * 0.7, f"loss did not drop: {l0} -> {l1}"


def test_suspend_taping_guard():
    """Inside the functionalized region, user record() must be inert:
    is_recording stays False under suspension and restores after."""
    from mxnet_tpu import _tape
    with _tape.suspend_taping():
        with autograd.record():
            assert not autograd.is_recording()
    with autograd.record():
        assert autograd.is_recording()


def test_compile_step_hybridized_net_inlines():
    """A hybridized (CachedOp) block must inline into the ONE fused step
    program rather than nesting cached dispatch — parity holds and only
    one step program compiles."""
    x, y = _batch()
    net_e = _build(with_bn=False)
    _run_eager(net_e, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
               x, y, steps=3)

    net_f = _build(with_bn=False)
    net_f.hybridize()
    trainer = Trainer(net_f.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net_f(a), b))
    for _ in range(3):
        step(x, y)
    assert step.mode == "fused" and step.n_traces == 1
    _assert_params_close(net_e, net_f, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# compiled-program structure (mx.analysis — ISSUE 4 acceptance)
# ---------------------------------------------------------------------------

def test_program_report_plain_fused_donates_everything(program_report):
    """dp=1 plain-fused mode, machine-checked: EVERY param/state buffer
    donated and actually aliased by XLA (no copy fallback), zero
    collectives, zero host transfers, zero dtype drift — the structural
    contract behind the writeback test above (which can't see a silent
    donation->copy regression: numerics stay right, HBM pays double)."""
    net = _build(with_bn=True)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    x, y = _batch()
    step(x, y)
    assert step.mode == "fused"
    rep = program_report(step, x, y)
    assert rep.mode == "fused"
    d = rep.donation
    # every param (incl. BN running stats) + every optimizer-state leaf
    assert d.expected == rep.meta["n_params"] + rep.meta["n_state_leaves"]
    assert d.aliased == d.expected, rep.summary()
    assert d.copied == [] and d.donated_bytes > 0
    assert rep.collectives.ops == []
    assert rep.host_transfers == [] and rep.dtype_drift == []
    assert rep.ok, rep.summary()


def test_program_report_fused_step_zero_stranded_ops(program_report):
    """ISSUE 9 structural acceptance: the plain fused MLP step's
    OPTIMIZED program carries a populated fusion census with ZERO
    fusable ops stranded between two fusions above the size floor —
    XLA fused everything it could, and the ideal-fusion diff
    (arXiv:2301.13062) stays silent.  A future change that fragments
    the step program (an op XLA stops fusing, a layout transpose
    between kernels) fails HERE, not as an MFU drop later."""
    net = _build(with_bn=True)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    x, y = _batch()
    step(x, y)
    rep = program_report(step, x, y)
    fr = rep.fusion
    assert fr is not None and fr.n_fusions > 0, rep.summary()
    assert fr.stranded == [], rep.summary()
    assert fr.boundary_bytes > 0          # kernels do exchange data
    assert all(k.kind in ("loop", "input", "output", "custom")
               for k in fr.fusions)
    assert rep.ok, rep.summary()


def test_program_report_donate_false_expects_nothing(program_report):
    """donate=False: the audit must not demand aliasing that was never
    requested."""
    net = _build(with_bn=False)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b),
                                donate=False)
    x, y = _batch()
    step(x, y)
    rep = program_report(step, x, y)
    assert rep.donation.expected is None
    assert rep.ok, rep.summary()
