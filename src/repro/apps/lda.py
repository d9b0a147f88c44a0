"""LDA topic modeling via collapsed Gibbs sampling on the parameter server.

The paper's second benchmark (NYT, K=100, 50% minibatch per clock).  The
shared PS state is the topic-word count table ``n_kw`` (K×V, additive count
deltas = INC updates); the doc-topic counts ``n_dk`` and topic assignments
``z`` are worker-local, exactly like Yahoo-LDA / ESSPTable's LDA app.  Each
clock a worker resamples a minibatch of its tokens against its (possibly
stale) view of ``n_kw``:

    p(z = k) ∝ (n_dk + α) (ñ_kw + β) / (ñ_k + Vβ)

and sends the count deltas to the server.  Sampling within a minibatch is
done against frozen counts (standard in distributed LDA samplers, e.g. plda)
— the PS staleness applies *between* clocks, which is what the paper
studies.  Quality metric: predictive log-likelihood of the whole corpus
under point estimates of θ, φ.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.ps import PSApp, fold_workers
from ..core.timemodel import TimeModel


def lda_time_model(**kw) -> TimeModel:
    """Paper-class wall-clock constants for the LDA/Gibbs app.

    A Gibbs clock resamples half of each worker's tokens, so it costs more
    compute than an SGD minibatch (t_comp = 0.2 s), while a producer's
    per-clock count deltas are sparser than MF factor rows (2 MB per
    channel).  Single source of truth for every LDA time axis (Fig 2,
    comm/comp split, auto-tuner).
    """
    kw.setdefault("t_comp", 0.2)
    kw.setdefault("bytes_per_channel", 2e6)
    return TimeModel(**kw)


@dataclass(frozen=True)
class LDAConfig:
    n_docs: int = 64          # total documents (divisible by n_workers)
    doc_len: int = 96         # tokens per document
    vocab: int = 200          # V
    n_topics: int = 10        # K
    true_topics: int = 10
    alpha: float = 0.5        # doc-topic prior
    beta: float = 0.1         # topic-word prior
    n_workers: int = 8
    minibatch_frac: float = 0.5   # fraction of local tokens per clock (paper: 50%)
    concentration: float = 0.05   # Dirichlet concentration of true topics
    seed: int = 0


def make_lda_app(cfg: LDAConfig) -> PSApp:
    P, K, V = cfg.n_workers, cfg.n_topics, cfg.vocab
    assert cfg.n_docs % P == 0
    docs_per = cfg.n_docs // P
    ntok = docs_per * cfg.doc_len                    # tokens per worker
    B = max(1, int(ntok * cfg.minibatch_frac))      # minibatch per clock

    rng = jax.random.PRNGKey(cfg.seed)
    k_phi, k_theta, k_words, k_z = jax.random.split(rng, 4)

    # --- synthetic corpus from a true topic model ------------------------
    phi_true = jax.random.dirichlet(
        k_phi, cfg.concentration * jnp.ones(V), (cfg.true_topics,))
    theta_true = jax.random.dirichlet(
        k_theta, 0.3 * jnp.ones(cfg.true_topics), (cfg.n_docs,))
    kz, kw = jax.random.split(k_words)
    z_true = jax.random.categorical(
        kz, jnp.log(theta_true)[:, None, :], axis=-1,
        shape=(cfg.n_docs, cfg.doc_len))
    words_all = jax.random.categorical(
        kw, jnp.log(phi_true)[z_true], axis=-1)     # [D, doc_len]

    # partition docs across workers
    words = words_all.reshape(P, docs_per * cfg.doc_len).astype(jnp.int32)
    docid = jnp.tile(
        jnp.repeat(jnp.arange(docs_per, dtype=jnp.int32), cfg.doc_len),
        (P, 1))

    # --- initial assignments and counts ----------------------------------
    z0 = jax.random.randint(k_z, (P, ntok), 0, K).astype(jnp.int32)

    def counts_for_worker(z_w, words_w, docid_w):
        onehot = jax.nn.one_hot(z_w, K)                       # [ntok, K]
        ndk = jnp.zeros((docs_per, K)).at[docid_w].add(onehot)
        nkw = jnp.zeros((K, V)).at[z_w, words_w].add(1.0)
        return ndk, nkw

    ndk0, nkw0_per = jax.vmap(counts_for_worker)(z0, words, docid)
    nkw0 = jnp.sum(nkw0_per, axis=0)                          # [K, V]

    def worker_update(view, local, _wid, clock, rng):
        nkw = view.reshape(K, V)
        # Clamp: staleness can transiently make counts locally negative;
        # real samplers clamp at read time too.
        nkw = jnp.maximum(nkw, 0.0)
        nk = jnp.sum(nkw, axis=-1)                            # [K]

        start = (clock * B) % ntok
        idx = (start + jnp.arange(B)) % ntok                  # rotating slice
        w = local["words"][idx]
        d = local["docid"][idx]
        zold = local["z"][idx]
        oh_old = jax.nn.one_hot(zold, K)                      # [B, K]

        ndk_tok = local["ndk"][d] - oh_old                    # exclude self
        nkw_tok = nkw[:, w].T - oh_old
        nk_tok = nk[None, :] - oh_old
        logits = (jnp.log(ndk_tok + cfg.alpha)
                  + jnp.log(jnp.maximum(nkw_tok, 0.0) + cfg.beta)
                  - jnp.log(jnp.maximum(nk_tok, 0.0) + V * cfg.beta))
        znew = jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
        oh_new = jax.nn.one_hot(znew, K)

        ndk = local["ndk"].at[d].add(oh_new - oh_old)
        z = local["z"].at[idx].set(znew)
        # INC deltas on the shared topic-word table.
        delta = (jnp.zeros((K, V)).at[znew, w].add(1.0)
                 .at[zold, w].add(-1.0))
        new_local = dict(local, z=z, ndk=ndk)
        return delta.ravel(), new_local

    def loss_sum(x, locals_, _worker_ids):
        """Negative predictive log-likelihood summed over each worker's
        tokens, and their count per worker."""
        nkw = jnp.maximum(x.reshape(K, V), 0.0)
        phi = (nkw + cfg.beta) / (jnp.sum(nkw, -1, keepdims=True) + V * cfg.beta)
        ndk = locals_["ndk"]                                  # [P, docs_per, K]
        theta = (ndk + cfg.alpha) / (
            jnp.sum(ndk, -1, keepdims=True) + K * cfg.alpha)
        w = locals_["words"]                                  # [P, ntok]
        d = locals_["docid"]
        # mixture likelihood per token: sum_k theta[d,k] phi[k,w]
        th = jnp.take_along_axis(
            theta, d[:, :, None], axis=1)                     # [P, ntok, K]
        ph = phi[:, w].transpose(1, 2, 0)                     # [P, ntok, K]
        ll = jnp.log(jnp.sum(th * ph, axis=-1) + 1e-30)
        return -jnp.sum(ll, axis=-1), ll.shape[1]

    def loss(x, locals_):
        """Negative predictive log-likelihood per token (lower = better)."""
        nll, count = loss_sum(x, locals_, None)
        return fold_workers(nll) / (count * P)

    local0 = {"words": words, "docid": docid, "z": z0, "ndk": ndk0}
    return PSApp(name="lda", dim=K * V, n_workers=P, x0=nkw0.ravel(),
                 local0=local0, worker_update=worker_update, loss=loss,
                 loss_sum=loss_sum)
