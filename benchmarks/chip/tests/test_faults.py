"""A whole run at a tiny size on the CPU, the harness's look for a chip
skipped, with the timed path broken underneath: ``correct`` has to come
out false for every fault a cell can have (one chip: no exchange between
chips to leave out), and true for the sound run."""
import numpy as np
import pytest

import run
import tiny

SEED = 2**31 + 99
CELLS = ("h2o-danube-1.8b.q8-local10", "granite-moe-1b-a400m.q8-local10")


def _step_fault(monkeypatch, broken):
    """Wrap the program's compiled train step as every client gets it."""
    from repro.train import steps

    get = steps.get_train_step

    def faulty(*a, **kw):
        fn = get(*a, **kw)
        return lambda state, batch: broken(fn, state, batch)

    monkeypatch.setattr(steps, "get_train_step", faulty)


def state_unchanged(monkeypatch):
    _step_fault(monkeypatch, lambda fn, s, b: (s, fn(s, b)[1]))


def half_batch(monkeypatch):
    def half(fn, s, b):
        return fn(s, {k: v[: v.shape[0] // 2] for k, v in b.items()})

    _step_fault(monkeypatch, half)


def update_doubled(monkeypatch):
    """The step's answer altered where it is produced: its first leaf
    moves twice as far as the step computed."""
    import jax

    def doubled(fn, s, b):
        new, m = fn(s, b)
        old, tree = jax.tree.flatten(s.params)
        cur = jax.tree.leaves(new.params)
        cur[0] = 2 * cur[0] - old[0]
        return new._replace(params=jax.tree.unflatten(tree, cur)), m

    _step_fault(monkeypatch, doubled)


def state_dropped(monkeypatch):
    """The optimizer state a site carries between rounds dropped: each
    round's first step starts from fresh moments and step 0."""
    import jax
    import jax.numpy as jnp

    def dropped(fn, s, b):
        if int(s.step) % tiny.LOCAL_STEPS == 0:
            s = s._replace(opt_state=jax.tree.map(jnp.zeros_like,
                                                  s.opt_state),
                           step=jnp.zeros_like(s.step))
        return fn(s, b)

    _step_fault(monkeypatch, dropped)


def uplink_altered(monkeypatch):
    """The q8 encoder's answer altered where it is produced: the first
    value of every frame one step off."""
    from repro.fl import messages

    enc = messages.quantize_int8

    def altered(x, *a, **kw):
        q, scales = enc(x, *a, **kw)
        q = q.copy()
        q[0] = q[0] - 1 if q[0] > -127 else q[0] + 1
        return q, scales

    monkeypatch.setattr(messages, "quantize_int8", altered)


def fit_exchange_left_out(monkeypatch):
    """The exchange between chips left out of a sharded fit step: each
    chip steps its own shard of the state (params and Adam moments) on
    its own rows of the batch alone, and no gradient crosses to another
    chip; a leaf that no chip shards keeps the first chip's copy."""
    import jax

    from repro.launch.shardings import state_shardings
    from repro.models import build_model
    from repro.train import steps

    get = steps.get_train_step

    def faulty(model_cfg, train_cfg, impl="xla", mesh=None):
        model = build_model(model_cfg)
        alone = jax.jit(steps.make_train_step(model, train_cfg, impl=impl))
        where = state_shardings(model, train_cfg, mesh)
        n = mesh.shape["data"]

        def data_axis(sh):
            return next((d for d, a in enumerate(sh.spec)
                         if "data" in (a if isinstance(a, tuple) else (a,))),
                        None)

        def gather(sh, *per_chip):
            d = data_axis(sh)
            if d is None:
                return jax.device_put(per_chip[0], sh)
            return jax.device_put(np.concatenate(
                [np.array_split(x, n, axis=d)[i]
                 for i, x in enumerate(per_chip)], axis=d), sh)

        def step(state, batch):
            # on the host and one device: no program spans the chips
            state = jax.device_get(state)
            rows = batch["tokens"].shape[0] // n
            outs = [jax.device_get(alone(state, {
                k: v[i * rows:(i + 1) * rows] for k, v in batch.items()}))
                for i in range(n)]
            return (jax.tree.map(gather, where, *[o[0] for o in outs]),
                    outs[0][1])

        return step if mesh is not None else get(model_cfg, train_cfg,
                                                 impl=impl, mesh=mesh)

    monkeypatch.setattr(steps, "get_train_step", faulty)


def fold_exchange_left_out(monkeypatch):
    """The exchange between chips left out of a sharded fold: only the
    first chip's range of the accumulator is gathered into the new
    model; the other ranges' partial sums never arrive, so they keep the
    round's base."""
    from repro.fl.agg_kernels import StreamingWeightedSum

    acc = StreamingWeightedSum._shard_acc

    def first_only(self, si):
        a = acc(self, si)
        return a if si == 0 else np.zeros_like(a)

    monkeypatch.setattr(StreamingWeightedSum, "_shard_acc", first_only)


def eval_half_batch(monkeypatch):
    """The evaluate's loss taken over half of the batch's rows."""
    from repro.train import steps

    make = steps.make_eval_step

    def half(*a, **kw):
        fn = make(*a, **kw)
        return lambda params, batch: fn(params, {
            k: v[: v.shape[0] // 2] for k, v in batch.items()})

    monkeypatch.setattr(steps, "make_eval_step", half)


def fold_answer_altered(monkeypatch):
    """One coordinate of the new global model moved by 4 fp32 ULPs."""
    from repro.fl.strategy import FedAvg

    opt = FedAvg._server_opt

    def altered(self, rnd, target, current):
        out = [np.array(a) for a in opt(self, rnd, target, current)]
        x = out[0].reshape(-1)
        for _ in range(4):
            x[0] = np.nextafter(x[0], np.float32(np.inf))
        return out

    monkeypatch.setattr(FedAvg, "_server_opt", altered)


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    res = run.run_cell(tiny.cell(workload), SEED, 1.0, False)
    assert res["correct"], res["checks"]
    # every window round sends each of the 4 sites a fit and an evaluate
    assert res["attempted"] > 0 and res["attempted"] % 8 == 0
    assert res["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   update_doubled, state_dropped,
                                   uplink_altered, fold_answer_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    res = run.run_cell(tiny.cell(workload), SEED, 1.0, False)
    assert not res["correct"], res["checks"]


# the cells whose limits compare the evaluate
EVAL_CELLS = [w for w in CELLS if "eval_loss_gap" in run.load_cell(w)["limits"]]


@pytest.mark.parametrize("workload", EVAL_CELLS)
def test_a_broken_evaluate_is_not_correct(monkeypatch, workload):
    eval_half_batch(monkeypatch)
    res = run.run_cell(tiny.cell(workload), SEED, 1.0, False)
    assert not res["correct"], res["checks"]
