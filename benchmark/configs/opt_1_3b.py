"""OPT-1.3B for serving: `zoo.transformer_lm` at the published sizes.

`build(sizes, seed, chips)` returns the initialised network and what the
`generate` driver and the reference need to know about it. Every size comes
from the JSON beside this file (the keys of facebook/opt-1.3b's config.json).
"""

from __future__ import annotations

source = "Zhang et al. 2022, arXiv:2205.01068, Table 1; facebook/opt-1.3b"


def build(sizes: dict, seed: int, chips: int) -> dict:
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.conf.enums import Updater
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    d, heads = int(sizes["hidden_size"]), int(sizes["num_attention_heads"])
    blocks, vocab = int(sizes["num_hidden_layers"]), int(sizes["vocab_size"])
    positions = int(sizes["max_position_embeddings"])
    if int(sizes["ffn_dim"]) != 4 * d:
        raise ValueError("transformer_lm's FFN is 4 x d_model")
    if sizes["activation_function"] != "relu" or not sizes[
            "do_layer_norm_before"] or int(sizes["word_embed_proj_dim"]) != d:
        raise ValueError("transformer_lm is pre-LN with a ReLU FFN and no "
                         "embedding projection")
    conf = zoo.transformer_lm(
        vocab_size=vocab, t=positions, d_model=d, n_heads=heads,
        n_blocks=blocks, decode_cache_length=positions,
        seed=seed % (2 ** 31 - 1))
    # Serving keeps no optimizer state (see `assumed` in the JSON).
    conf.global_conf.updater = Updater.NONE
    for vertex in conf.vertices.values():
        layer = getattr(vertex, "layer", None)
        if layer is not None:
            layer.updater = Updater.NONE
    conf.global_conf.dtype_policy = sizes["dtype_policy"]
    net = ComputationGraph(conf).init()
    return {"net": net, "vocab": vocab, "capacity": positions,
            "n_heads": heads, "n_blocks": blocks,
            "page_size": int(sizes["kv_page_size"])}
