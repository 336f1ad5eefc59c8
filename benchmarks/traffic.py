"""The one traffic generator.  A traffic mix is a data file of parameters
(`workloads/<cell>.json`, key `params`); this module turns it and `--seed`
into requests or batches.  Every seed gets the same set of sizes in another
order, so two runs differ in order and token ids, not in work."""

from __future__ import annotations

import functools
import statistics

import numpy as np

PAIRING = 7  # the seed of the one permutation that pairs prompt and answer lengths


def lognormal_quantiles(median, sigma, lo, hi, n):
    """n whole-number lengths at the (i + 0.5) / n quantiles of a lognormal,
    clipped to [lo, hi]: the mix's shape without a draw's luck."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def request_pool(params):
    """[(prompt_len, answer_len)]: `pool` pairs, the same for every seed.
    Prompt and answer lengths are paired by one fixed permutation, so that
    they are independent of each other."""
    n = int(params["pool"])
    p, a = params["prompt_len"], params["answer_len"]
    prompts = lognormal_quantiles(p["median"], p["sigma"], p["min"], p["max"], n)
    answers = lognormal_quantiles(a["median"], a["sigma"], a["min"], a["max"], n)
    answers = answers[np.random.default_rng(PAIRING).permutation(n)]
    cap = int(params["max_total"])
    return [(int(x), int(min(y, cap - x))) for x, y in zip(prompts, answers)]


def request_stream(params, seed, vocab_size):
    """Endless (prompt ids, answer_len): the pool in a seeded order, again
    and again in a new order; token ids uniform over the vocabulary, no two
    prompts sharing anything."""
    pool = request_pool(params)
    rng = np.random.default_rng([int(seed), 1])
    while True:
        for i in rng.permutation(len(pool)):
            n, m = pool[i]
            yield rng.integers(1, vocab_size, size=n, dtype=np.int64).astype(np.int32), m


@functools.lru_cache(maxsize=None)
def _batch_fn(batch, seqlen, vocab_size):
    import jax

    @jax.jit
    def f(key, step):
        k = jax.random.fold_in(key, step)
        return jax.random.randint(k, (batch, seqlen + 1), 0, vocab_size, "int32")

    return f


def train_tokens(seed, step, batch, seqlen, vocab_size):
    """[batch, seqlen + 1] token ids of step `step`, made on the device:
    inputs are [:, :-1], next-token labels [:, 1:]."""
    from .weights import seed_key

    return _batch_fn(int(batch), int(seqlen), int(vocab_size))(seed_key(seed), int(step))
