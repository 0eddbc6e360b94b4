"""The benchmark's own input generators, driven by a traffic file.

Copies of the program's seeded generators, kept here so that a change
to the program cannot change the yardstick:

* :func:`lm_batch` follows ``repro.data.synthetic.synthetic_lm_batch``
  (a head of frequent tokens shifted per agent, a uniform tail);
* :func:`logreg_data` follows ``repro.core.problem.make_logreg_problem``
  (Gaussian features with an offset per agent, labels from a random
  ground truth with noise).

Everything is made on the device, in one jitted call, from ``--seed``.
"""

from __future__ import annotations

import functools


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``: the low 32 bits make the key
    and the rest are folded in, so seeds past 2**32 do not collide."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                              seed // 2**32)


def lm_batch(key, vocab: int, n_agents: int, seqs: int, seq_len: int,
             head_share: float, agent_shift: int):
    """One round's batch: ``tokens`` and next-token ``labels``, each
    ``(n_agents, seqs, seq_len)`` int32.  Agent ``i`` draws 70-90 % of
    its tokens from a head of ``head_share * vocab`` frequent ids shifted
    by ``i * agent_shift``, the rest uniformly (non-IID agents)."""
    import jax
    import jax.numpy as jnp

    def one(k, i):
        k_head, k_tail, k_coin = jax.random.split(k, 3)
        skew = i.astype(jnp.float32)
        head = jax.random.randint(k_head, (seqs, seq_len), 0,
                                  max(2, int(vocab * head_share)))
        tail = jax.random.randint(k_tail, (seqs, seq_len), 0, vocab)
        coin = jax.random.bernoulli(k_coin, 0.7 + 0.2 * jnp.tanh(skew),
                                    (seqs, seq_len))
        tokens = jnp.where(coin, (head + i * agent_shift) % vocab, tail)
        return {"tokens": tokens.astype(jnp.int32),
                "labels": jnp.roll(tokens, -1, axis=-1).astype(jnp.int32)}

    keys = jax.random.split(key, n_agents)
    return jax.vmap(one)(keys, jnp.arange(n_agents, dtype=jnp.int32))


def lm_pool(seed: int, vocab: int, fed: dict, traffic: dict):
    """``traffic["pool"]`` distinct round batches, made in one jitted
    call; round ``r`` of the run takes ``pool[r % len(pool)]``."""
    import jax

    gen = functools.partial(
        lm_batch, vocab=vocab, n_agents=fed["n_agents"],
        seqs=fed["seqs_per_agent"], seq_len=fed["seq_len"],
        head_share=traffic["head_share"],
        agent_shift=traffic["agent_shift"])

    @jax.jit
    def make(key):
        return tuple(gen(jax.random.fold_in(key, r))
                     for r in range(traffic["pool"]))

    return list(jax.block_until_ready(make(seed_key(seed))))


def logreg_data(seed: int, n_agents: int, q: int, dim: int,
                heterogeneity: float):
    """Features ``A`` ``(N, q, n)`` float32 and labels ``b`` ``(N, q)`` in
    {-1, +1}."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        truth = jax.random.normal(k1, (dim,))
        offsets = heterogeneity * jax.random.normal(k2, (n_agents, 1, dim))
        A = jax.random.normal(k3, (n_agents, q, dim)) + offsets
        logits = jnp.einsum("nqd,d->nq", A, truth,
                            precision=jax.lax.Precision.HIGHEST)
        noise = 0.5 * jax.random.normal(k4, (n_agents, q))
        b = jnp.where(logits + noise > 0, 1.0, -1.0)
        return A, b

    return jax.block_until_ready(make(seed_key(seed)))
