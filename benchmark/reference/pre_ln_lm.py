"""Plain reference: the forward pass of a pre-LN decoder-only transformer LM
(OPT's block: LayerNorm -> causal multi-head attention -> residual;
LayerNorm -> ReLU FFN of 4 x d -> residual; final LayerNorm; linear head),
in straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`. No cache, no kernels, no
batching, no padding tricks: one sequence in, one `[T, V]` table of
next-token probabilities out. It is fed the served network's own parameters
(cast to float32), under the names `zoo.transformer_lm` gives them.

Departures from OPT as published (facebook/opt-1.3b), all the program's and
listed in the configuration's file: the output head is its own matrix, not
the embedding's transpose; the position table has no offset of 2; the key
projection has no bias (a bias on the keys moves every score of a row by
the same amount, and the softmax does not see it).
"""

from __future__ import annotations

EPS = 1e-5  # LayerNormalization.eps of the program's layer conf


def _layer_norm(x, p):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * p["gamma"] + p["beta"]


def _attention(x, p, n_heads: int):
    import jax
    import jax.numpy as jnp

    t, d = x.shape
    dh = d // n_heads
    q = (x @ p["Wq"] + p["qB"]).reshape(t, n_heads, dh)
    k = (x @ p["Wk"]).reshape(t, n_heads, dh)
    v = (x @ p["Wv"] + p["vB"]).reshape(t, n_heads, dh)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", weights, v).reshape(t, d)
    return out @ p["Wo"] + p["oB"]


def forward(params: dict, ids, n_heads: int, n_blocks: int):
    """`ids` [T] int -> probabilities [T, V], float32."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        t = ids.shape[0]
        x = p["emb"]["W"][ids] + p["pos"]["P"][:t]
        for i in range(n_blocks):
            x = x + _attention(_layer_norm(x, p[f"ln_a{i}"]),
                               p[f"attn{i}"], n_heads)
            h = _layer_norm(x, p[f"ln_f{i}"])
            h = jax.nn.relu(h @ p[f"ff1_{i}"]["W"] + p[f"ff1_{i}"]["b"])
            x = x + h @ p[f"ffn{i}"]["W"] + p[f"ffn{i}"]["b"]
        x = _layer_norm(x, p["ln_out"])
        logits = x @ p["out"]["W"] + p["out"]["b"]
        return jax.nn.softmax(logits, axis=-1)


def greedy_agreement(probs_of, prompt, served, n_new: int, ratio: float,
                     comparable=lambda token: True):
    """Served greedy ids against the reference's argmax.

    `probs_of(ids)` is the reference's next-token distribution after `ids`.
    Token by token along the SERVED sequence: the served token either is the
    reference's argmax, or (a near-tie: two correct programs that round
    differently part there) has at least `ratio` of the reference's best
    probability; after a fork nothing more is compared, because the two
    sequences are no longer the same question. `comparable(token)` says
    whether the program can condition on `token` as given (see
    `drivers/generate.py`: a program that rounds token ids cannot); after
    the first token it cannot, nothing more is compared either. Returns
    `(verdict, worst_ratio, tokens_compared)`, verdict one of "equal",
    "near_tie", or a string that starts with "differs".
    (chip_smoke.py::greedy_agreement's rule.)"""
    served = [int(t) for t in served]
    if served[:len(prompt)] != [int(t) for t in prompt]:
        return "differs: the prompt came back changed", 0.0, 0
    if len(served) != len(prompt) + n_new:
        return (f"differs: {len(served) - len(prompt)} new tokens, "
                f"{n_new} asked"), 0.0, 0
    worst, compared = 1.0, 0
    for i in range(len(prompt), len(served)):
        probs = probs_of(served[:i])
        best = int(probs.argmax())
        compared += 1
        if served[i] != best:
            r = float(probs[served[i]] / probs[best])
            worst = min(worst, r)
            if r < ratio:
                return (f"differs at {i}: served {served[i]} has {r:.4f} of "
                        f"the reference's best ({best})"), worst, compared
            return "near_tie", worst, compared
        if not comparable(served[i]):
            break
    return "equal", worst, compared
