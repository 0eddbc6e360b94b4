"""Training driver.

Runs Fed-PLT (default) or standard FSDP training of any assigned
architecture on the local devices (smoke/real) -- the multi-pod
configuration is exercised by dryrun.py.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 20 --mode fed
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
      --smoke --steps 10 --mode standard --optimizer adamw
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.checkpoint import (checkpoint_extra, find_latest_checkpoint,
                              save_checkpoint, restore_checkpoint)
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.data.synthetic import make_batch_for
from repro.fed import api
from repro.launch.cache import enable_compile_cache
from repro.models.model import build_model
from repro.optim import adamw, apply_updates, momentum, sgd


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="fed", choices=["fed", "standard"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (2 layers, d_model 256)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--local-dataset-size", type=int, default=None,
                    help="smallest local dataset size q_i for the "
                         "privacy report (default: per-agent batch)")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="fed mode: save the round state to "
                         "<checkpoint>/rounds/step-NNNNNN every N rounds "
                         "(atomic tmp-then-rename saves; 0 = off)")
    ap.add_argument("--resume", action="store_true",
                    help="fed mode: resume from the latest committed "
                         "round checkpoint under <checkpoint>/rounds "
                         "(bit-for-bit: per-round keys are derived by "
                         "fold_in, so the continued run matches an "
                         "uninterrupted one)")
    # every fed knob is generated from the FedSpec fields -- new spec
    # fields / registered compressors become flags without edits here
    api.add_spec_args(ap)
    args = ap.parse_args()

    spec = api.spec_from_args(args)
    if args.mode == "fed":
        spec.validate()      # fail fast, before building the model
    if (args.checkpoint_every or args.resume) and not args.checkpoint:
        ap.error("--checkpoint-every/--resume require --checkpoint")
    if (args.checkpoint_every or args.resume) and args.mode != "fed":
        ap.error("--checkpoint-every/--resume are fed-mode only")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    shape = InputShape("cli", args.seq_len, args.batch, "train")
    key = jax.random.PRNGKey(0)

    if args.mode == "fed":
        trainer = api.build_trainer(model, spec)
        # --agent-shards / --mesh-shape are generated spec flags; the
        # trainer builds the (agent, model) round mesh from them
        mesh = getattr(trainer, "mesh", None)
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            print(f"mesh: {sizes} over {mesh.devices.size} devices "
                  f"(agent axis sharded)")
        if spec.privacy.tau > 0:
            # every DP run states its (eps, delta) position up front
            # make_batch_for splits the global batch across agents
            q = args.local_dataset_size or max(1, args.batch
                                               // spec.n_agents)
            rep = trainer.privacy_report(args.steps, q)
            caveat = "" if spec.privacy.clip is not None else \
                " (UNCLIPPED: per-sample sensitivity assumed 1.0 -- " \
                "pass --clip)"
            print(f"privacy: ({rep.adp_eps:.3f}, {rep.adp_delta:.0e})-ADP"
                  f" over K={rep.K} rounds x N_e={rep.n_epochs};"
                  f" ceiling as K*Ne->inf: eps={rep.eps_ceiling:.3f}"
                  f" at Renyi order {rep.rdp_order:.1f}{caveat}")
            if rep.per_agent:
                # heterogeneous run: the headline eps above is the max
                # over this per-agent (eps_i, delta) table (Prop. 4)
                for a in rep.per_agent:
                    print(f"  agent {a.agent:3d}: q_i={a.q} "
                          f"N_e={a.n_epochs} gamma={a.gamma:.4g} "
                          f"eps_i={a.adp_eps:.3f} "
                          f"(ceiling {a.eps_ceiling:.3f})")
        state = trainer.init(key)
        stale = spec.async_mode != "off"
        arrival_rows = []   # realized (N,) rows -> the run's schedule
        start_round = 0
        rounds_dir = (os.path.join(args.checkpoint, "rounds")
                      if args.checkpoint else None)
        if args.resume:
            latest = find_latest_checkpoint(rounds_dir)
            if latest is None:
                print(f"resume: no committed checkpoint under "
                      f"{rounds_dir} -- starting from round 0")
            else:
                shards = (trainer._state_shardings()
                          if mesh is not None else None)
                state = restore_checkpoint(latest, state, shards)
                meta_extra = checkpoint_extra(latest) or {}
                start_round = int(meta_extra.get("round", 0))
                arrival_rows = [np.asarray(r, np.float32)
                                for r in meta_extra.get("arrivals", [])]
                print(f"resumed from {latest} at round {start_round}")
        for i in range(start_round, args.steps):
            batch = make_batch_for(cfg, shape, jax.random.fold_in(key, i),
                                   n_agents=spec.n_agents)
            t0 = time.time()
            state, metrics = trainer.step(state, batch,
                                          jax.random.fold_in(key, i))
            extra = ""
            if stale:
                arrival_rows.append(np.asarray(metrics["arrivals"]))
                extra = f" stale={float(metrics['staleness']):.2f}"
            print(f"round {i:4d} loss={float(metrics['loss']):.4f} "
                  f"part={float(metrics['participation']):.2f}{extra} "
                  f"dt={time.time() - t0:.2f}s")
            if (args.checkpoint_every
                    and (i + 1) % args.checkpoint_every == 0):
                ck = os.path.join(rounds_dir, f"step-{i + 1:06d}")
                save_checkpoint(
                    ck, state, step=i + 1,
                    extra={"round": i + 1,
                           "arrivals": [np.asarray(r).tolist()
                                        for r in arrival_rows]})
                print(f"  checkpointed round {i + 1} -> {ck}")
        if stale and spec.privacy.tau > 0 and arrival_rows:
            # the nominal table above charged every agent the full K
            # rounds; recompose over the REALIZED arrival schedule --
            # each agent over the rounds of local work it released
            q = args.local_dataset_size or max(1, args.batch
                                               // spec.n_agents)
            rep = api.effective_privacy_report(
                spec, np.stack(arrival_rows), q)
            print(f"effective privacy (realized arrival schedule, "
                  f"max_staleness={spec.max_staleness}): "
                  f"({rep.adp_eps:.3f}, {rep.adp_delta:.0e})-ADP")
            for a in rep.per_agent:
                print(f"  agent {a.agent:3d}: arrivals={a.arrivals} "
                      f"released_rounds={a.K}/{rep.K} "
                      f"eps_i={a.adp_eps:.3f} "
                      f"(ceiling {a.eps_ceiling:.3f})")
        final = trainer.consensus(state)
    else:
        params = model.init(key)
        opt = {"sgd": sgd(args.lr), "momentum": momentum(args.lr),
               "adamw": adamw(args.lr)}[args.optimizer]
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss_fn(p, batch=batch))(params)
            upd, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, upd), opt_state, loss

        for i in range(args.steps):
            batch = make_batch_for(cfg, shape, jax.random.fold_in(key, i))
            t0 = time.time()
            params, opt_state, loss = step(params, opt_state, batch)
            print(f"step {i:4d} loss={float(loss):.4f} "
                  f"dt={time.time() - t0:.2f}s")
        final = params

    if args.checkpoint:
        target = args.checkpoint
        if args.mode == "fed" and (args.checkpoint_every or args.resume):
            # rolling round checkpoints live under <checkpoint>/rounds;
            # save_checkpoint atomically REPLACES its target directory,
            # so the consensus save gets a sibling entry instead of
            # clobbering the whole tree
            target = os.path.join(args.checkpoint, "consensus")
        save_checkpoint(target, final, step=args.steps)
        print(f"saved checkpoint to {target}")
    n = sum(x.size for x in jax.tree_util.tree_leaves(final))
    print(f"done: {args.arch} ({n/1e6:.2f}M params)")


if __name__ == "__main__":
    main()
