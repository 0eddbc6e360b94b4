"""Driver of the model cells: Fed-PLT rounds of a decoder, through the
program's front door (``FedSpec`` -> ``build_trainer``).

Set-up builds the trainer and its state from the seed, makes the
traffic's pool of round batches on the device, and drives the first
three rounds through the trainer's own ``step`` -- the call the window
makes, on pool batches that all differ.  Those rounds compile and warm
the round, and their losses and state changes are what the reference
is held against.  The window then goes on with the same trainer and
state, reading each round's loss back to the host, as
``ModelTrainer.run`` does.  After the window the program's state is
freed and the reference (``bench/reference/transformer.py``) runs the
same three rounds in float32.

Compared, each beside its limit from ``bench/limits/<workload>.json``:

* ``loss_gap``: largest relative gap of a round's loss (the mean over
  agents of the last local epoch's loss), rounds 1-3;
* ``update1_gap``: gap of norms of the round-1 change of ``x`` (the
  local solver's first output), worst leaf;
* ``change3_gap``: gap of norms of the change of ``x`` and ``z`` after
  three rounds (both edges and the solver), worst leaf.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp

from bench import compare, flops, harness, traffic, window
from bench.reference import transformer as ref

CHECK_ROUNDS = 3
SPANS = ("round.dispatch", "round.readback")


def program_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if model["hidden_act"] != "silu" or not model["tie_word_embeddings"]:
        raise harness.NoResult("the model driver runs gated-SiLU decoders "
                               "with tied embeddings")
    d, h = model["hidden_size"], model["num_attention_heads"]
    return ModelConfig(
        name=model["name"], family="dense",
        n_layers=model["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim", d // h),
        d_ff=model["intermediate_size"], vocab=model["vocab_size"],
        pattern=("global",), activation="swiglu",
        rope_theta=model["rope_theta"], tie_embeddings=True,
        norm_eps=model["rms_norm_eps"], dtype=model["torch_dtype"])


def fed_spec(fed: dict):
    """The federation's semantics; implementation knobs (state layout,
    edge backend, kernels) stay at the front door's defaults."""
    from repro.fed.api import CompressionSpec, FedSpec, PrivacySpec

    return FedSpec(
        n_agents=fed["n_agents"], rho=fed["rho"],
        participation=fed["participation"], damping=fed["damping"],
        solver=fed["solver"], n_epochs=fed["n_epochs"],
        gamma=fed["gamma"],
        privacy=PrivacySpec(tau=fed["tau"], clip=fed["clip"]),
        compression=CompressionSpec(name=fed["compression"]),
        aggregator=fed["aggregator"],
        aggregator_param=fed["aggregator_param"])


def _reference_follows(fed: dict) -> None:
    plain = dict(participation=1.0, solver="gd", tau=0.0, clip=None,
                 compression="none", aggregator="mean")
    for k, v in plain.items():
        if fed[k] != v:
            raise harness.NoResult(
                f"the reference follows {k}={v!r} only; the cell asks "
                f"for {fed[k]!r}")


def build(model: dict, fed: dict, wkey):
    """The program's trainer over the benchmark's seeded weights."""
    from repro.fed import api
    from repro.models.model import build_model

    dtype = jnp.dtype(model["torch_dtype"])
    prog = build_model(program_config(model))
    want = jax.eval_shape(lambda: ref.init_params(wkey, model, dtype))
    have = jax.eval_shape(prog.init, wkey)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree_util.tree_leaves(want),
                jax.tree_util.tree_leaves(have))):
        raise harness.NoResult("the program's parameter tree is not the "
                               "layout bench/reference/transformer.py reads")
    prog = dataclasses.replace(
        prog, init=lambda key: ref.init_params(key, model, dtype))
    return api.build_trainer(prog, fed_spec(fed))


def keys(seed: int):
    """(weights key, round key) of a seed."""
    base = traffic.seed_key(seed)
    return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)


def theta0(model: dict, wkey):
    """The seeded starting weights, made again, in float32."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.init_params(wkey, model, jnp.dtype(model["torch_dtype"])))


def first_rounds(trainer, model: dict, wkey, rkey, pool):
    """Drive the first rounds through ``trainer.step``; returns the
    state after them and ``(losses, round-1 change of x, change of x and
    z after the last)`` as leaf norms.  The starting weights are made
    again for the norms rather than kept: a second copy of the state
    would not fit beside the round at float32."""
    state = trainer.init(wkey)
    losses, update1 = [], None
    for i in range(CHECK_ROUNDS):
        state, m = trainer.step(state, pool[i], jax.random.fold_in(rkey, i))
        losses.append(float(m["loss"]))
        if i == 0:
            update1 = compare.leaf_norms(state.x, theta0(model, wkey))
    start = theta0(model, wkey)
    change3 = {**{"x" + k: v for k, v in
                  compare.leaf_norms(state.x, start).items()},
               **{"z" + k: v for k, v in
                  compare.leaf_norms(state.z, start).items()}}
    return state, (losses, update1, change3)


def reference_rounds(model: dict, fed: dict, wkey, pool, precision="f32"):
    """The same readings from the reference (another ``precision``: a
    control)."""
    start = theta0(model, wkey)
    update1, change3 = {}, {}

    def on_round(rr, xs, zs):
        if rr == 1:
            update1.update(compare.stacked_norms(
                [compare.leaf_norms(x, start) for x in xs]))
        if rr == CHECK_ROUNDS:
            for tag, trees in (("x", xs), ("z", zs)):
                change3.update({tag + k: v for k, v in
                                compare.stacked_norms(
                                    [compare.leaf_norms(t, start)
                                     for t in trees]).items()})

    losses = ref.run_rounds(start, pool[:CHECK_ROUNDS], model, fed,
                            precision=precision, on_round=on_round)
    return losses, update1, change3


def gaps(prog, reference) -> dict:
    (pl, pu, pc), (rl, ru, rc) = prog, reference
    return {"loss_gap": compare.rel_gap(pl, rl),
            "update1_gap": compare.norm_gap(pu, ru),
            "change3_gap": compare.norm_gap(pc, rc)}


def cell_fed(cell) -> dict:
    fed = {**cell.fed, "seq_len": cell.traffic["seq_len"],
           "seqs_per_agent": cell.traffic["seqs_per_agent"]}
    _reference_follows(fed)
    return fed


def run(r) -> harness.Outcome:
    cell = r.cell
    model, fed = cell.config, cell_fed(cell)
    wkey, rkey = keys(r.seed)
    trainer = build(model, fed, wkey)
    pool = traffic.lm_pool(r.seed, model["vocab_size"], fed, cell.traffic)
    state, prog = first_rounds(trainer, model, wkey, rkey, pool)
    setup_s = time.perf_counter() - r.start

    # -- the window: the same trainer and state ---------------------------
    failed = []
    r_next = [CHECK_ROUNDS]

    def one(_, spans):
        nonlocal state
        i = r_next[0]
        with spans.span("round.dispatch"):
            state, metrics = trainer.step(state, pool[i % len(pool)],
                                          jax.random.fold_in(rkey, i))
        with spans.span("round.readback"):
            loss = float(metrics["loss"])
        failed.append(not math.isfinite(loss))
        r_next[0] += 1

    meas = window.measure(one, r.seconds, r.trace, SPANS,
                          cell.traffic.get("trace_seconds"))
    peak = int(r.devices[0].memory_stats()["peak_bytes_in_use"]) \
        if r.devices[0].platform == "tpu" else None
    del state, trainer
    gc.collect()

    # -- the reference, after the window --------------------------------
    found = gaps(prog, reference_rounds(model, fed, wkey, pool))
    checks = [harness.Check(k, v, cell.limits[k]) for k, v in found.items()]
    readings = {"window_s": meas.seconds, "units": meas.units,
                "spans": meas.spans, "trace": meas.trace,
                "flops_per_unit": flops.train_flops_per_round(model, fed),
                "peak_bytes": peak, "device_kind": r.devices[0].device_kind}
    return harness.Outcome(
        end_to_end={"round_ms": 1e3 * meas.seconds / meas.units},
        attempted=meas.units, failed=sum(failed), checks=checks,
        readings=readings, setup_s=setup_s, trace=meas.trace,
        peak_bytes=peak)
