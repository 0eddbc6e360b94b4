"""The program's telemetry: host spans, compile counters booked to the
open span, the op -> scope map of a compiled round, and named scopes
that leave the round's bits as they were."""

import glob
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.problem import make_logreg_problem
from repro.core.solvers import SolverConfig
from repro.data.synthetic import make_batch_for
from repro.fed import engine, telemetry
from repro.fed.api import FedSpec, build_trainer
from repro.fed.compress import pack_leaves, packed_meta
from repro.fed.solvers import make_packed_local_solver
from repro.models.model import build_model

COMPILE_FIELDS = ("trace_s", "lower_s", "backend_compile_s",
                  "cache_load_s", "compiles", "cache_hits", "cache_misses")


def _window(fn):
    """``fn()``'s result and the registry's change while it ran."""
    before = telemetry.snapshot()
    out = fn()
    return out, telemetry.diff(telemetry.snapshot(), before)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_counts_parents_and_self_time():
    def work():
        for _ in range(2):
            with telemetry.span("t.outer"):
                time.sleep(0.002)
                with telemetry.span("t.inner"):
                    time.sleep(0.004)

    _, d = _window(work)
    outer, inner = d["t.outer"], d["t.inner"]
    assert outer["count"] == 2 and inner["count"] == 2
    assert outer["parent"] is None and inner["parent"] == "t.outer"
    assert inner["self_s"] == pytest.approx(inner["host_s"])
    # the outer span's own time is its time less what its child covered
    assert outer["self_s"] == pytest.approx(
        outer["host_s"] - inner["host_s"], abs=1e-9)
    assert outer["self_s"] >= 0.004 and inner["host_s"] >= 0.008


def test_span_books_even_when_the_body_raises():
    def work():
        with pytest.raises(ValueError):
            with telemetry.span("t.raises"):
                raise ValueError("boom")

    _, d = _window(work)
    assert d["t.raises"]["count"] == 1


def test_fresh_jit_books_one_compile_to_the_open_span():
    x = jnp.arange(8.0)
    f = jax.jit(lambda a: a * 3.0 + 1.0)

    def call():
        with telemetry.span("t.compile"):
            return jax.block_until_ready(f(x))

    _, first = _window(call)
    assert first["t.compile"]["compiles"] == 1
    assert first["t.compile"]["backend_compile_s"] > 0
    _, second = _window(call)
    assert second["t.compile"]["compiles"] == 0
    assert second["t.compile"]["count"] == 1


def test_warm_dense_run_books_every_compile_to_run():
    prob = make_logreg_problem(n_agents=4, q=10, dim=5, seed=0)
    tr = build_trainer(prob, FedSpec(gamma=0.05))
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(tr.run(key, 3))
    _, d = _window(lambda: jax.block_until_ready(tr.run(key, 3)))
    assert d["fedplt.run"]["count"] == 1
    for name, rec in d.items():
        if name != "fedplt.run":
            assert not any(rec[f] for f in COMPILE_FIELDS), (name, rec)


def test_dense_run_compiles_once_per_length():
    prob = make_logreg_problem(n_agents=4, q=10, dim=5, seed=0)
    # partial participation: the key draws which agents take part
    tr = build_trainer(prob, FedSpec(gamma=0.05, participation=0.5))
    key, other = jax.random.PRNGKey(0), jax.random.PRNGKey(1)

    def run(k, n_rounds):
        return lambda: jax.block_until_ready(tr.run(k, n_rounds))

    (first, _), cold = _window(run(key, 3))
    assert cold["fedplt.run"]["compiles"] == 1
    (again, _), warm = _window(run(key, 3))
    assert warm["fedplt.run"]["compiles"] == 0
    np.testing.assert_array_equal(np.asarray(again.x), np.asarray(first.x))
    (moved, _), new_key = _window(run(other, 3))
    assert new_key["fedplt.run"]["compiles"] == 0
    assert not np.array_equal(np.asarray(moved.x), np.asarray(first.x))
    _, longer = _window(run(key, 4))
    assert longer["fedplt.run"]["compiles"] == 1
    for d in (cold, warm, new_key, longer):
        assert d["fedplt.run"]["count"] == 1


def test_dense_trainer_spans():
    prob = make_logreg_problem(n_agents=4, q=10, dim=5, seed=0)
    key = jax.random.PRNGKey(0)

    def work():
        tr = build_trainer(prob, FedSpec(gamma=0.05))
        state = tr.init(key)
        for _ in range(2):
            state = tr.step(state)
        tr.run_recorded(key, 2)
        return jax.block_until_ready(state)

    _, d = _window(work)
    assert d["fedplt.build"]["count"] == 1
    assert d["fedplt.init"]["count"] == 1
    assert d["fedplt.step"]["count"] == 2
    assert d["fedplt.run"]["count"] == 1
    assert all(d[n]["parent"] is None for n in
               ("fedplt.build", "fedplt.init", "fedplt.step", "fedplt.run"))


def test_spans_are_written_into_a_profiler_trace(tmp_path):
    prob = make_logreg_problem(n_agents=4, q=10, dim=5, seed=0)
    tr = build_trainer(prob, FedSpec(gamma=0.05))
    state = tr.init(jax.random.PRNGKey(0))
    jax.block_until_ready(tr.step(state))
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        state = tr.step(state)
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    steps = [dict(ev.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "fedplt.step"]
    assert len(steps) == 2
    # rounds are numbered by the span's host-side count
    assert steps[1]["step_num"] == steps[0]["step_num"] + 1


# ---------------------------------------------------------------------------
# the model trainer: spans, and the round it lowers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_trainer():
    cfg = get_config("gemma2-2b").reduced()
    tr = build_trainer(build_model(cfg), FedSpec(n_agents=2, n_epochs=1,
                                                 gamma=0.05))
    batch = make_batch_for(cfg, InputShape("tiny", 16, 4, "train"),
                           n_agents=2)
    return tr, batch


def test_model_trainer_step_books_no_compile_once_warm(model_trainer):
    tr, batch = model_trainer
    key = jax.random.PRNGKey(0)
    state = tr.init(key)
    state, _ = tr.step(state, batch, key)

    def rounds():
        s = state
        for i in range(2):
            s, m = tr.step(s, batch, jax.random.fold_in(key, i))
        return float(m["loss"])

    _, d = _window(rounds)
    assert d["fedplt.step"]["count"] == 2
    assert d["fedplt.step"]["compiles"] == 0


def test_model_trainer_run_nests_init_and_steps(model_trainer):
    tr, batch = model_trainer
    _, d = _window(lambda: tr.run(jax.random.PRNGKey(1), 2,
                                  lambda i: batch))
    assert d["fedplt.run"]["count"] == 1
    assert d["fedplt.step"]["count"] == 2
    assert d["fedplt.step"]["parent"] == "fedplt.run"
    assert d["fedplt.init"]["parent"] == "fedplt.run"
    assert d["fedplt.run"]["self_s"] < d["fedplt.run"]["host_s"]


def test_model_trainer_lowers_the_round_it_steps(model_trainer):
    tr, batch = model_trainer
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(tr.init, key)
    scopes = set(telemetry.op_scopes(
        tr.lower(state, batch, key).compile().as_text()).values())
    assert {"fedplt.uplink", "fedplt.local_solver",
            "fedplt.downlink"} <= scopes


# ---------------------------------------------------------------------------
# a tiny round on both layouts: its scopes, and its bits
# ---------------------------------------------------------------------------

N = 4
ROBUST_INT8 = dict(compression="int8", aggregator="trimmed_mean", param=1)


def tiny_round(layout, compression="none", aggregator="mean", param=0.0):
    """A jitted round of 4 agents on a two-leaf tree, gd on a quadratic,
    participation 0.75, and its first arguments."""
    cfg = engine.RoundConfig(n_agents=N, rho=0.5, damping=0.8,
                             participation=0.75, compression=compression,
                             aggregator=aggregator, aggregator_param=param)
    ka, kb = jax.random.split(jax.random.PRNGKey(11))
    x = {"a": jax.random.normal(ka, (N, 8)),
         "b": jax.random.normal(kb, (N, 3, 5))}
    target = jax.tree_util.tree_map(lambda l: 0.5 * l + 1.0, x)
    fgrad = lambda w, k: jax.tree_util.tree_map(jnp.subtract, w, target)
    scfg = SolverConfig(name="gd", n_epochs=3, step_size=0.1)
    if layout == "tree":
        solver = engine.make_local_solver(scfg, fgrad, cfg.rho)
        step = lambda x, z, t, k: engine.round_step(cfg, x, z, t, k, solver)
    else:
        meta = packed_meta(x)
        solver = make_packed_local_solver(scfg, fgrad, cfg.rho, meta=meta)
        step = lambda x, z, t, k: engine.packed_round_step(
            cfg, meta, x, z, t, k, solver)
        x = pack_leaves(x)[0]
    return jax.jit(step), (x, x, x, jax.random.PRNGKey(5))


@pytest.mark.parametrize("layout", ["tree", "packed"])
@pytest.mark.parametrize("kw", [{}, ROBUST_INT8], ids=["plain", "robust-int8"])
def test_op_scopes_find_the_phases_of_a_round(layout, kw):
    step, args = tiny_round(layout, **kw)
    ops = telemetry.op_scopes(step.lower(*args).compile().as_text())
    want = {"fedplt.uplink", "fedplt.local_solver", "fedplt.downlink"}
    if kw:
        want |= {"fedplt.compress", "fedplt.aggregate"}
    assert set(ops.values()) == want
    assert {m for m, _ in ops} == {"jit__lambda"}


def test_op_scopes_read_the_innermost_scope():
    text = ('HloModule jit_step, is_scheduled=true\n'
            '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(step)/fedplt.local_solver/while/body/'
            'transpose(jvp(fedplt.compress))/mul" stack_frame_id=2}\n'
            '  ROOT %add.1 = f32[4]{0} add(%a, %b), metadata={op_type="add" '
            'op_name="jit(step)/fedplt.uplink/add"}\n'
            '  %copy.2 = f32[4]{0} copy(%a), metadata={op_name="jit(step)/copy"}\n'
            '  %param.1 = f32[4]{0} parameter(0)\n')
    assert telemetry.op_scopes(text) == {
        ("jit_step", "fusion.3"): "fedplt.compress",
        ("jit_step", "add.1"): "fedplt.uplink"}


def test_compiled_memory_counts_aliases_and_remat_clones():
    x = jnp.ones((64, 64))
    f = lambda a, b: (a * 2, b + 1)  # noqa: E731
    donated = jax.jit(f, donate_argnums=0).lower(x, x).compile()
    kept = telemetry.compiled_memory(jax.jit(f).lower(x, x).compile())
    mem = telemetry.compiled_memory(donated)
    assert mem["argument_bytes"] == 2 * x.nbytes
    assert mem["alias_bytes"] == x.nbytes and kept["alias_bytes"] == 0
    assert mem["remat_clones"] == kept["remat_clones"] == 0

    class Rematerialised:
        """The text of a program XLA cloned ops in to fit memory."""
        memory_analysis = donated.memory_analysis

        @staticmethod
        def as_text():
            return ('  %fusion.464 = f32[4]{0} fusion(%p), kind=kLoop\n'
                    '  %fusion.464.remat3 = f32[4]{0} fusion(%p), '
                    'metadata={op_name="jit(step)/mul"}\n'
                    '  %gte.7 = f32[4]{0} get-tuple-element(%t), '
                    'metadata={op_name="x.remat"}\n'
                    '  ROOT %copy.331.remat_compressed = f32[4]{0} '
                    'copy(%a)\n')

    assert telemetry.compiled_memory(Rematerialised())["remat_clones"] == 2


# sha256 (first 16 hex digits) of x, z, t, y, u over two rounds, recorded
# before the round engine carried any named scope
CHECKSUMS = {("tree", "plain"): "5cd08e7c655a8575",
             ("tree", "robust-int8"): "29490887ead12c26",
             ("packed", "plain"): "bf69286ce206709d",
             ("packed", "robust-int8"): "8b22691042cdc929"}


@pytest.mark.parametrize("layout,case", sorted(CHECKSUMS))
def test_scoped_round_keeps_its_bits(layout, case):
    step, (x, z, t, key) = tiny_round(layout,
                                      **(ROBUST_INT8 if case != "plain"
                                         else {}))
    h = hashlib.sha256()
    for _ in range(2):
        res = step(x, z, t, key)
        x, z, t, key = res.x, res.z, res.t, res.next_key
        for leaf in jax.tree_util.tree_leaves(
                (res.x, res.z, res.t, res.y, res.u)):
            h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == CHECKSUMS[(layout, case)]
