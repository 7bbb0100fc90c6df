"""Model zoo: the reference-designated benchmark configs (BASELINE.md).

- LeNet-MNIST (reference: dl4j-examples LenetMnistExample — MultiLayerNetwork)
- MLP-MNIST (the minimal end-to-end slice)
- GravesLSTM char-RNN (reference: GravesLSTMCharModellingExample)
- VGG-16 (reference: Keras-import VGG16 zoo, `keras/trainedmodels/TrainedModels.java:16-19`)
- AlexNet (reference: the LRN layer's model family, `conf/layers/LocalResponseNormalization.java`)

All built through the public config DSL, so they double as integration tests
of the builder.
"""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.nn.conf.enums import Updater
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    ConvolutionLayer,
    DenseLayer,
    GravesLSTM,
    LocalResponseNormalization,
    OutputLayer,
    RnnOutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.conf.neural_net import (
    MultiLayerConfiguration,
    NeuralNetConfiguration,
)


def mlp_mnist(seed: int = 123, lr: float = 0.006) -> MultiLayerConfiguration:
    """Two-layer MLP on flat 28x28 inputs."""
    return (
        NeuralNetConfiguration.builder()
        .seed(seed).learning_rate(lr).updater(Updater.NESTEROVS).momentum(0.9)
        .weight_init("xavier").l2(1e-4)
        .list()
        .layer(DenseLayer(n_out=1000, activation="relu"))
        .layer(OutputLayer(n_out=10, activation="softmax", loss_function="negativeloglikelihood"))
        .set_input_type(InputType.feed_forward(784))
        .build()
    )


def lenet_mnist(seed: int = 123, lr: float = 0.01, dtype: str = "float32") -> MultiLayerConfiguration:
    """LeNet: conv5x5x20 -> maxpool -> conv5x5x50 -> maxpool -> dense500 -> softmax10."""
    return (
        NeuralNetConfiguration.builder()
        .seed(seed).learning_rate(lr).updater(Updater.NESTEROVS).momentum(0.9)
        .weight_init("xavier").l2(5e-4).activation("identity").dtype(dtype)
        .list()
        .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1), n_out=20, activation="identity"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1), n_out=50, activation="identity"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(DenseLayer(n_out=500, activation="relu"))
        .layer(OutputLayer(n_out=10, activation="softmax", loss_function="negativeloglikelihood"))
        .set_input_type(InputType.convolutional(28, 28, 1))
        .build()
    )


def char_rnn(
    vocab_size: int = 77, hidden: int = 200, layers: int = 2,
    tbptt_length: int = 50, seed: int = 12345, dtype: str = "float32",
) -> MultiLayerConfiguration:
    """GravesLSTM character model (reference example: 2x200 LSTM + RnnOutput)."""
    builder = (
        NeuralNetConfiguration.builder()
        .seed(seed).learning_rate(0.1).updater(Updater.RMSPROP).rms_decay(0.95)
        .weight_init("xavier").l2(0.001).dtype(dtype)
        .list()
    )
    for _ in range(layers):
        builder.layer(GravesLSTM(n_out=hidden, activation="tanh"))
    builder.layer(RnnOutputLayer(n_out=vocab_size, activation="softmax", loss_function="mcxent"))
    return (
        builder
        .backprop_type("truncatedbptt")
        .t_bptt_forward_length(tbptt_length)
        .t_bptt_backward_length(tbptt_length)
        .set_input_type(InputType.recurrent(vocab_size))
        .build()
    )


def vgg16(n_classes: int = 1000, seed: int = 123, dtype: str = "bfloat16") -> MultiLayerConfiguration:
    """VGG-16 (configuration matches the Keras VGG16 the reference imports)."""
    def conv(n):
        return ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                convolution_mode="same", n_out=n, activation="relu")

    def pool():
        return SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2))

    b = (
        NeuralNetConfiguration.builder()
        .seed(seed).learning_rate(0.01).updater(Updater.NESTEROVS).momentum(0.9)
        .weight_init("relu").dtype(dtype)
        .list()
    )
    for block, (n, reps) in enumerate([(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]):
        for _ in range(reps):
            b.layer(conv(n))
        b.layer(pool())
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(OutputLayer(n_out=n_classes, activation="softmax", loss_function="mcxent"))
    return b.set_input_type(InputType.convolutional(224, 224, 3)).build()


def alexnet(n_classes: int = 1000, seed: int = 123, image: int = 224,
            dtype: str = "bfloat16") -> MultiLayerConfiguration:
    """AlexNet (Krizhevsky et al. 2012) — the model family the reference's
    LocalResponseNormalization layer exists for
    (`nn/conf/layers/LocalResponseNormalization.java` cites it) and the
    dl4j-era examples' large-image CNN: conv11x11/4 + LRN + pool,
    conv5x5 + LRN + pool, 3x conv3x3, pool, two dense-4096, softmax."""
    return (
        NeuralNetConfiguration.builder()
        .seed(seed).learning_rate(0.01).updater(Updater.NESTEROVS)
        .momentum(0.9).weight_init("xavier").l2(5e-4).dtype(dtype)
        .list()
        .layer(ConvolutionLayer(kernel_size=(11, 11), stride=(4, 4),
                                n_out=96, activation="relu",
                                convolution_mode="truncate"))
        .layer(LocalResponseNormalization())
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                stride=(2, 2)))
        .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1),
                                n_out=256, activation="relu",
                                convolution_mode="same"))
        .layer(LocalResponseNormalization())
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                stride=(2, 2)))
        .layer(ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                n_out=384, activation="relu",
                                convolution_mode="same"))
        .layer(ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                n_out=384, activation="relu",
                                convolution_mode="same"))
        .layer(ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                n_out=256, activation="relu",
                                convolution_mode="same"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                stride=(2, 2)))
        .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
        .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
        .layer(OutputLayer(n_out=n_classes, activation="softmax",
                           loss_function="negativeloglikelihood"))
        .set_input_type(InputType.convolutional(image, image, 3))
        .build()
    )



def _add_transformer_block(gb, prev, i, d_model, n_heads, *, causal,
                           moe=False, n_experts=4,
                           decode_cache_length=None, norm=None, attn=None,
                           ffn=None):
    """One pre-norm transformer block: x + Attn(N(x)); x + FFN(N(x)).
    Shared by `transformer_lm` (causal, optional MoE/KV cache),
    `transformer_classifier` (bidirectional) and `sparse_moe_lm`, which
    passes its own pieces: `norm()` makes a norm layer (default
    `LayerNormalization`), `attn` and `ffn` are the block's attention and
    feed-forward layers."""
    import copy

    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        LayerNormalization, MoELayer, SelfAttentionLayer,
    )

    norm = norm or LayerNormalization
    gb.add_layer(f"ln_a{i}", norm(), prev)
    gb.add_layer(f"attn{i}",
                 copy.deepcopy(attn) if attn is not None else
                 SelfAttentionLayer(
                     n_out=d_model, n_heads=n_heads, causal=causal,
                     decode_cache_length=decode_cache_length), f"ln_a{i}")
    gb.add_vertex(f"res_a{i}", ElementWiseVertex(op="add"), prev, f"attn{i}")
    gb.add_layer(f"ln_f{i}", norm(), f"res_a{i}")
    if ffn is not None:
        gb.add_layer(f"ffn{i}", copy.deepcopy(ffn), f"ln_f{i}")
    elif moe:
        gb.add_layer(f"ffn{i}",
                     MoELayer(n_out=d_model, n_experts=n_experts,
                              expert_hidden=4 * d_model, top_k=2,
                              router_jitter=1e-2), f"ln_f{i}")
    else:
        gb.add_layer(f"ff1_{i}", DenseLayer(n_out=4 * d_model,
                                            activation="relu"), f"ln_f{i}")
        gb.add_layer(f"ffn{i}", DenseLayer(n_out=d_model,
                                           activation="identity"),
                     f"ff1_{i}")
    gb.add_vertex(f"res_f{i}", ElementWiseVertex(op="add"),
                  f"res_a{i}", f"ffn{i}")
    return f"res_f{i}"


def transformer_lm(vocab_size: int, *, t: int = 64, d_model: int = 64,
                   n_heads: int = 4, n_blocks: int = 2, moe: bool = False,
                   n_experts: int = 4, seed: int = 123, lr: float = 3e-3,
                   dtype: str = "float32", decode_cache_length=None):
    """Decoder-only transformer language model built through the config DSL
    (ComputationGraph: residual adds around causal SelfAttentionLayer and
    an FFN — DenseLayer pair, or MoELayer when `moe`).

    No reference equivalent (the reference predates attention; its
    language model is the GravesLSTM char-RNN above) — this is the
    round-5 model-family face of the SURVEY §2.3/§5 parallelism
    extensions: the same config trains sequence-sharded
    (`ParallelWrapper(..., seq_axis=...)` -> ring attention) or
    expert-parallel (`expert_axis=...`) with zero model changes.

    `decode_cache_length=N` sizes every attention layer's KV cache (and
    the positional table) for O(1)-per-token stateful generation via
    `ComputationGraph.rnn_time_step` / `generate_lm(use_cache=True)`.
    """
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingLayer,
        LayerNormalization,
        MoELayer,
        PositionalEmbeddingLayer,
        SelfAttentionLayer,
    )

    gb = (NeuralNetConfiguration.builder()
          .seed(seed).learning_rate(lr).updater(Updater.ADAM).dtype(dtype)
          .weight_init("xavier")
          .graph_builder()
          .add_inputs("tokens")
          .add_layer("emb", EmbeddingLayer(n_out=d_model, has_bias=False,
                                           input_format="ids",
                                           activation="identity"), "tokens")
          .add_layer("pos", PositionalEmbeddingLayer(
              max_length=max(t, 16, decode_cache_length or 0),
              stateful=decode_cache_length is not None), "emb"))
    prev = "pos"
    for i in range(n_blocks):
        prev = _add_transformer_block(
            gb, prev, i, d_model, n_heads, causal=True, moe=moe,
            n_experts=n_experts, decode_cache_length=decode_cache_length)
    gb.add_layer("ln_out", LayerNormalization(), prev)
    gb.add_layer("out", RnnOutputLayer(n_out=vocab_size,
                                       activation="softmax",
                                       loss_function="mcxent"), "ln_out")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size, t))
    return gb.build()


def sparse_moe_lm(vocab_size: int, *, t: int, d_model: int, n_blocks: int,
                  n_heads: int, n_kv_heads: Optional[int] = None,
                  head_dim: Optional[int] = None,
                  n_experts: int, top_k: int, expert_hidden: int,
                  experts_held=None, index_top_k=None, index_n_heads=None,
                  index_head_dim=None, rope_theta: float = 1e7,
                  rms_eps: float = 1e-6, norm_topk_prob: bool = True,
                  aux_loss_weight: float = 1e-3, lr: float = 1e-5,
                  adam_mean_decay: float = 0.9, adam_var_decay: float = 0.95,
                  seed: int = 123, dtype_policy=None, layer_types=None,
                  attention_types=None, latent_attention=None,
                  first_dense: int = 0, dense_hidden: Optional[int] = None,
                  scoring=None, routed_scaling_factor=None,
                  shared_hidden=None):
    """Decoder-only mixture-of-experts language model (the Qwen3-MoE block;
    with DeepSeek sparse attention's indexer as Keye-VL-2.0-30B-A3B's
    language model has it, or with sliding-window and full layers in a
    pattern as Mellum2-12B-A2.5B has them; or the DeepSeek-V3 block as
    Kimi-VL-A3B's language model has it), built from DSL layers and
    trained by `ComputationGraph.fit` on integer ids `[B, T]` with integer
    next-token labels `[B, T]`:

        emb -> n_blocks x [ x + Attn(RMSNorm(x)); x + MoE(RMSNorm(x)) ]
            -> RMSNorm -> untied head (no bias), softmax cross-entropy

    Attn: `n_heads` query heads of `head_dim` over `n_kv_heads` key/value
    heads, no biases, RMS norm on each q and k head, rotate-half RoPE; with
    `index_top_k` an indexer of `index_n_heads` x `index_head_dim` keeps
    that many keys per query, and its leaves are frozen (`nn/layers/dsa.py`).
    `layer_types`: the kind of each block's attention, a list as long as
    `n_blocks` or one period of it (`["sliding_attention"] * 3 +
    ["full_attention"]`), and `attention_types` what each kind changes of
    the `SelfAttentionLayer` above (`{"sliding_attention":
    {"sliding_window": 1024}, "full_attention": {"rope_scaling": {...}}}`:
    `sliding_window`, `rope_theta`, `rope_scaling`); without them every
    block is alike. A layer without an indexer runs the registry's
    `banded_attention`: the causal triangle or the window's band, the mask
    from iotas inside the kernel and no `[S, S]` array anywhere.
    `latent_attention`: multi-head latent attention in place of the
    grouped-query heads, its `SelfAttentionLayer` fields under their
    `transformers` names (`{"kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128}`):
    a compressed key/value projection under an RMS norm, a rotary part that
    is a slice of each query head and ONE rotary key head for all, no
    QK-norm; `n_kv_heads` and `head_dim` are then left out. It runs the
    registry's `latent_attention`.
    MoE: `n_experts` gated SiLU experts of `expert_hidden`, `top_k` per
    token, dropless; `experts_held = (first, count)` keeps only those
    experts' weights here and computes their part of the sum
    (`parallel/expert.py::moe_ffn_dropless`). `scoring="sigmoid"` with
    `routed_scaling_factor`: the router scores each expert by its own
    sigmoid, chooses by score + a frozen float32 selection bias (`gate_b`,
    zeros until set), weighs by the unbiased scores and balances per
    sequence (`MoELayer.scoring`); `shared_hidden`: a shared expert of that
    width beside the routed ones. `first_dense`: that many leading blocks
    have a dense gated SiLU MLP of `dense_hidden` (`GatedDenseLayer`) where
    the others have experts. `vocab_size` is the number of embedding and
    head rows held (a slice of the model's vocabulary).

    Device-trace scopes: `dsa.indexer`, `dsa.select`, `dsa.attend` (under an
    indexer), `attn.sliding`, `attn.full` (without), `mla.project`,
    `mla.attend` (latent attention), `attn.rope`, `moe.route`,
    `moe.experts`, `moe.shared`, `ffn.dense`, `lm.head`."""
    import dataclasses

    from deeplearning4j_tpu.nn.conf.distributions import NormalDistribution
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingLayer, GatedDenseLayer, MoELayer, RMSNormalization,
        SelfAttentionLayer,
    )

    nb = (NeuralNetConfiguration.builder()
          .seed(seed).learning_rate(lr).updater(Updater.ADAM)
          .adam_mean_decay(adam_mean_decay).adam_var_decay(adam_var_decay)
          .weight_init("xavier"))
    if dtype_policy is not None:
        nb = nb.dtype_policy(dtype_policy)
    gb = (nb.graph_builder()
          .add_inputs("tokens")
          # Embedding rows of unit variance (`torch.nn.Embedding`'s default):
          # a token's own row then outweighs what random attention adds to
          # every row alike, and a random router spreads its load; Xavier
          # over [vocab, d_model] rows are a hundredth of that, and every
          # token then went to the same few experts (PERF.md PR 26).
          .add_layer("emb", EmbeddingLayer(
              n_out=d_model, has_bias=False, input_format="ids",
              activation="identity", weight_init="distribution",
              dist=NormalDistribution(0.0, 1.0)), "tokens"))
    attn = SelfAttentionLayer(
        n_out=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
        head_dim=head_dim, causal=True, rope_theta=float(rope_theta),
        **(latent_attention if latent_attention is not None else dict(
            qk_norm_eps=float(rms_eps), index_top_k=index_top_k,
            index_n_heads=index_n_heads, index_head_dim=index_head_dim)))
    ffn = MoELayer(
        n_out=d_model, n_experts=n_experts, expert_hidden=expert_hidden,
        top_k=top_k, dropless=True,
        norm_topk_prob=norm_topk_prob, aux_loss_weight=aux_loss_weight,
        experts_held=None if experts_held is None else tuple(experts_held),
        scoring=scoring, routed_scaling_factor=routed_scaling_factor,
        shared_hidden=shared_hidden)
    dense = GatedDenseLayer(n_out=d_model, hidden=dense_hidden or 0,
                            scope="ffn.dense")
    kinds = list(layer_types or [None])
    if n_blocks % len(kinds):
        raise ValueError(f"layer_types has {len(kinds)} entries: n_blocks "
                         f"({n_blocks}) is not a whole number of periods")
    prev = "emb"
    for i, kind in enumerate(kinds * (n_blocks // len(kinds))):
        prev = _add_transformer_block(
            gb, prev, i, d_model, n_heads, causal=True,
            norm=lambda: RMSNormalization(eps=rms_eps),
            ffn=dense if i < first_dense else ffn,
            attn=attn if kind is None else dataclasses.replace(
                attn, **(attention_types or {}).get(kind, {})))
    gb.add_layer("ln_out", RMSNormalization(eps=rms_eps), prev)
    gb.add_layer("out", RnnOutputLayer(n_out=vocab_size, has_bias=False,
                                       activation="softmax",
                                       loss_function="mcxent",
                                       scope="lm.head"), "ln_out")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size, t))
    return gb.build()


def zaya_moe_lm(vocab_size: int, *, t: int, d_model: int, n_blocks: int,
                n_heads: int, n_kv_heads: int, head_dim: int,
                n_experts: int, top_k: int, expert_hidden: int,
                router_hidden: int, experts_held=None,
                rope_theta: float = 5e6, partial_rotary_factor: float = 0.5,
                cca_time0: int = 2, cca_time1: int = 2, rms_eps: float = 1e-5,
                aux_loss_weight: float = 1e-3, embedding_std: float = 1.0,
                lr: float = 1e-5, adam_mean_decay: float = 0.9,
                adam_var_decay: float = 0.95, seed: int = 123,
                dtype_policy=None):
    """Decoder-only mixture-of-experts language model of the ZAYA1 block,
    built from DSL layers and trained by `ComputationGraph.fit` on integer
    ids `[B, T]` with integer next-token labels `[B, T]`:

        emb -> n_blocks x [ x = a_a x + b_a CCA(RMSNorm(x));
                            r_i = EDA(RMSNorm(x) W_d, r_{i-1});
                            x = a_f x + b_f MoE(RMSNorm(x), r_i) ]
            -> RMSNorm -> head tied to the embedding, softmax cross-entropy

    CCA: compressed convolutional attention of `n_heads` query heads over
    `n_kv_heads` key/value heads of `head_dim` (`nn/layers/cca.py`: two
    causal convolutions of `cca_time0` and `cca_time1` taps, the q-k mean,
    L2-normalised q and k with a temperature on k, rotary on the first
    `partial_rotary_factor` of each head at `rope_theta`, values half from
    the previous position). `a_*`, `b_*`: learned per-channel gains
    (`ScaledResidualLayer`, `res_a<i>` / `res_f<i>`). The router's state
    `route<i>` (`RouterStateLayer`: a projection to `router_hidden` columns,
    averaged with the previous block's state, exponential depth averaging,
    from the second block on) is an edge of the graph into block i's
    `ffn<i>` and block i + 1's `route<i+1>`. MoE: `n_experts` gated SiLU
    experts of `expert_hidden`, dropless, routed by an MLP over the state
    to `n_experts + 1` choices, the last a skip that no device holds, the
    `top_k` best chosen and weighted by their unrenormalised softmax
    probabilities; `experts_held = (first, count)` keeps only those experts
    here. The head reads the embedding's table (`tied_to`): one leaf, its
    gradient the sum of both uses. `embedding_std`: the table's init (a
    tied head's logits have std `embedding_std * sqrt(d_model)` at init).
    `vocab_size` is the number of rows held (a slice of the vocabulary).

    Device-trace scopes: `cca.project`, `cca.conv`, `cca.attend`,
    `moe.route` (the router's state and its MLP), `moe.experts`, `lm.head`.
    """
    from deeplearning4j_tpu.nn.conf.distributions import NormalDistribution
    from deeplearning4j_tpu.nn.conf.layers import (
        CompressedConvAttentionLayer, EmbeddingLayer, MoELayer,
        RMSNormalization, RouterStateLayer, ScaledResidualLayer,
    )

    nb = (NeuralNetConfiguration.builder()
          .seed(seed).learning_rate(lr).updater(Updater.ADAM)
          .adam_mean_decay(adam_mean_decay).adam_var_decay(adam_var_decay)
          .weight_init("xavier"))
    if dtype_policy is not None:
        nb = nb.dtype_policy(dtype_policy)
    gb = (nb.graph_builder()
          .add_inputs("tokens")
          .add_layer("emb", EmbeddingLayer(
              n_out=d_model, has_bias=False, input_format="ids",
              activation="identity", weight_init="distribution",
              dist=NormalDistribution(0.0, float(embedding_std))), "tokens"))
    norm = lambda: RMSNormalization(eps=rms_eps)  # noqa: E731
    prev = "emb"
    for i in range(n_blocks):
        gb.add_layer(f"ln_a{i}", norm(), prev)
        gb.add_layer(f"attn{i}", CompressedConvAttentionLayer(
            n_out=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, rope_theta=float(rope_theta),
            partial_rotary_factor=float(partial_rotary_factor),
            cca_time0=cca_time0, cca_time1=cca_time1), f"ln_a{i}")
        gb.add_layer(f"res_a{i}", ScaledResidualLayer(), prev, f"attn{i}")
        gb.add_layer(f"ln_f{i}", norm(), f"res_a{i}")
        gb.add_layer(f"route{i}", RouterStateLayer(
            n_out=router_hidden, carry=i > 0),
            f"ln_f{i}", *([f"route{i - 1}"] if i else []))
        gb.add_layer(f"ffn{i}", MoELayer(
            n_out=d_model, n_experts=n_experts, expert_hidden=expert_hidden,
            top_k=top_k, dropless=True, norm_topk_prob=False,
            aux_loss_weight=aux_loss_weight,
            experts_held=None if experts_held is None
            else tuple(experts_held),
            router_hidden=router_hidden),
            f"ln_f{i}", f"route{i}")
        gb.add_layer(f"res_f{i}", ScaledResidualLayer(), f"res_a{i}",
                     f"ffn{i}")
        prev = f"res_f{i}"
    gb.add_layer("ln_out", norm(), prev)
    gb.add_layer("out", RnnOutputLayer(n_out=vocab_size, has_bias=False,
                                       activation="softmax",
                                       loss_function="mcxent",
                                       tied_to="emb", scope="lm.head"),
                 "ln_out")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size, t))
    return gb.build()


def _sample_token(probs, rng, temperature: float, top_k: int, top_p: float):
    """Sample one next-token id from a [V] probability vector (greedy at
    temperature<=0; top-k / nucleus top-p restrictions compose, applied
    before temperature). Tokens excluded by top-k/top-p are masked to
    -inf in logit space so re-tempering can NEVER re-admit them."""
    import numpy as np

    probs = np.asarray(probs, np.float64)
    if temperature <= 0:
        return int(probs.argmax())
    if top_k:
        kth = np.sort(probs)[-min(top_k, len(probs))]
        probs = np.where(probs >= kth, probs, 0.0)
    if top_p:
        order = np.argsort(-probs)
        csum = np.cumsum(probs[order]) - probs[order]
        cut = order[csum >= top_p * probs.sum()]
        probs = probs.copy()
        probs[cut] = 0.0
    logits = np.log(np.maximum(probs, 1e-12)) / temperature
    logits[probs <= 0] = -np.inf
    p = np.exp(logits - logits.max())
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


def _sample_tokens(probs, rng, temperature: float, top_k: int):
    """Batched `_sample_token`: [B, V] probabilities -> [B] ids, one rng
    draw per row (same draw order as a Python loop over rows, so seeded
    generations are reproducible)."""
    import numpy as np

    probs = np.asarray(probs, np.float64)
    if temperature <= 0:
        return probs.argmax(-1)
    if top_k:
        kth = np.sort(probs, axis=-1)[:, -min(top_k, probs.shape[-1])]
        probs = np.where(probs >= kth[:, None], probs, 0.0)
    logits = np.log(np.maximum(probs, 1e-12)) / temperature
    # Same exclusion mask as the single-sequence path: without it,
    # temperature > 1 re-inflates the log(1e-12) floor of excluded tokens
    # and batched top-k can sample outside the top k.
    logits[probs <= 0] = -np.inf
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.asarray([rng.choice(p.shape[-1], p=p[i])
                       for i in range(p.shape[0])])


def generate_lm(cg, prompt_ids, n_steps: int, *, window: int,
                temperature: float = 1.0, seed: int = 0,
                use_cache: bool = False, top_k: int = 0,
                top_p: float = 0.0):
    """Autoregressive sampling from a `transformer_lm` ComputationGraph
    (reference analog: GravesLSTMCharModellingExample's
    sampleCharactersFromNetwork).

    Two modes:
    - `use_cache=False`: re-read the window each token — the context is
      right-padded to `window` (one compiled shape) and the next-token
      distribution read at the last real position; O(window) attention
      per token.
    - `use_cache=True` (model built with `decode_cache_length`): stateful
      O(1)-per-token decode via `ComputationGraph.rnn_time_step` — prime
      once with the prompt, then single-token steps against the KV cache,
      exactly like the reference's RNN sampling loop.

    `temperature=0` is greedy argmax; `top_k`/`top_p` restrict sampling to
    the k most probable tokens / the smallest nucleus with cumulative
    probability >= p (composable; applied before temperature). Returns
    prompt + generated ids.
    """
    import numpy as np

    rng = np.random.RandomState(seed)
    ids = list(int(i) for i in prompt_ids)
    if not ids:
        raise ValueError("need at least one prompt token")

    def pick(probs):
        return _sample_token(probs, rng, temperature, top_k, top_p)

    if use_cache:
        cache_lens = [
            v.layer.decode_cache_length
            for v in cg.layer_vertices.values()
            if type(v.layer).__name__ == "SelfAttentionLayer"
        ]
        if not cache_lens or any(c is None for c in cache_lens):
            raise ValueError(
                "use_cache=True needs a model built with "
                "transformer_lm(..., decode_cache_length=N)")
        if len(ids) + n_steps > min(cache_lens):
            raise ValueError(
                f"prompt ({len(ids)}) + n_steps ({n_steps}) exceeds the "
                f"decode cache capacity {min(cache_lens)}")
        if n_steps == 0:
            return ids
        cg.rnn_clear_previous_state()
        out = cg.rnn_time_step(
            np.asarray(ids, np.float32)[None, :, None])[0]  # [1, Tp, V]
        ids.append(pick(out[0, -1]))
        for _ in range(n_steps - 1):
            out = cg.rnn_time_step(
                np.asarray([[[float(ids[-1])]]], np.float32))[0]
            ids.append(pick(out[0, -1] if out.ndim == 3 else out[0]))
        return ids

    for _ in range(n_steps):
        ctx = ids[-window:]
        # [1, T, 1] index layout: unambiguous for EmbeddingLayer (a 2-D
        # float [1, window] would be misread as one-hot when window
        # happens to equal vocab_size).
        x = np.zeros((1, window, 1), np.float32)
        x[0, : len(ctx), 0] = ctx
        out = cg.output_single(x)  # [1, T, V] per-step softmax
        ids.append(pick(out[0, len(ctx) - 1]))
    return ids


def transformer_classifier(vocab_size: int, n_classes: int, *, t: int = 64,
                           d_model: int = 64, n_heads: int = 4,
                           n_blocks: int = 2, seed: int = 123,
                           lr: float = 3e-3, dtype: str = "float32"):
    """Bidirectional transformer encoder + mean-pool + softmax head — the
    sequence-classification sibling of `transformer_lm` (BERT-shaped:
    non-causal attention over the whole sequence). Feature masks flow
    through attention (key masking) and the mask-aware global pooling, so
    ragged sequences classify correctly.
    """
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingLayer,
        GlobalPoolingLayer,
        LayerNormalization,
        PositionalEmbeddingLayer,
        SelfAttentionLayer,
    )

    gb = (NeuralNetConfiguration.builder()
          .seed(seed).learning_rate(lr).updater(Updater.ADAM).dtype(dtype)
          .weight_init("xavier")
          .graph_builder()
          .add_inputs("tokens")
          .add_layer("emb", EmbeddingLayer(n_out=d_model, has_bias=False,
                                           input_format="ids",
                                           activation="identity"), "tokens")
          .add_layer("pos", PositionalEmbeddingLayer(max_length=max(t, 16)),
                     "emb"))
    prev = "pos"
    for i in range(n_blocks):
        prev = _add_transformer_block(gb, prev, i, d_model, n_heads,
                                      causal=False)
    gb.add_layer("ln_out", LayerNormalization(), prev)
    gb.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), "ln_out")
    gb.add_layer("out", OutputLayer(n_out=n_classes, activation="softmax",
                                    loss_function="mcxent"), "pool")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size, t))
    return gb.build()


def generate_lm_batch(cg, prompts, n_steps: int, *, temperature: float = 1.0,
                      seed: int = 0, top_k: int = 0):
    """KV-cached batched generation: `prompts` is [B, Tp] (equal-length
    int prompts); every sequence decodes in the SAME single-token steps,
    so the per-token cost is one dispatch for the whole batch — the
    serving shape of the decode path. Returns [B, Tp + n_steps] ids.

    Requires a model built with `decode_cache_length >= Tp + n_steps`.
    """
    import numpy as np

    rng = np.random.RandomState(seed)
    prompts = np.asarray(prompts, np.int64)
    if prompts.ndim != 2 or prompts.shape[1] < 1:
        raise ValueError("prompts must be [B, Tp] with Tp >= 1")
    B, Tp = prompts.shape
    cache_lens = [v.layer.decode_cache_length
                  for v in cg.layer_vertices.values()
                  if type(v.layer).__name__ == "SelfAttentionLayer"]
    if not cache_lens or any(c is None for c in cache_lens):
        raise ValueError("generate_lm_batch needs decode_cache_length")
    if Tp + n_steps > min(cache_lens):
        raise ValueError(
            f"Tp ({Tp}) + n_steps ({n_steps}) exceeds the decode cache "
            f"capacity {min(cache_lens)}")

    def pick(probs):  # probs: [B, V] -> [B]
        return _sample_tokens(probs, rng, temperature, top_k)

    out = [prompts]
    cg.rnn_clear_previous_state()
    step_out = cg.rnn_time_step(
        prompts.astype(np.float32)[:, :, None])[0]  # [B, Tp, V]
    for _ in range(n_steps):
        nxt = pick(step_out[:, -1])
        out.append(nxt[:, None])
        step_out = cg.rnn_time_step(
            nxt.astype(np.float32)[:, None, None])[0]  # [B, 1, V]
    return np.concatenate(out, axis=1)


def decode_cache_capacity(cg) -> int:
    """Smallest `decode_cache_length` across the graph's attention layers —
    the hard per-sequence step budget. Raises when the model was built
    without a KV cache.

    Both decode layouts share this budget: the dense `DecodeStepper`
    allocates it up front per slot, while `PagedDecodeStepper` backs it
    with pool pages (`models/kv_pool.py`) allocated as a sequence
    deepens — capacity must then be a multiple of the page size."""
    caps = [v.layer.decode_cache_length
            for v in cg.layer_vertices.values()
            if type(v.layer).__name__ == "SelfAttentionLayer"]
    if not caps or any(c is None for c in caps):
        raise ValueError(
            "model has no KV cache; build it with "
            "transformer_lm(..., decode_cache_length=N)")
    return min(caps)


class DecodeStepper:
    """Step-granular decode entry point for a `transformer_lm` graph — the
    seam the serving tier's continuous-batching scheduler drives.

    `generate_lm_batch` advances B sequences in lockstep from prompt to
    finish: a new request must wait for the whole batch to drain. This
    class instead owns a fixed-width batch of `slots` whose per-slot KV
    caches and cursors live in ONE batched rnn-state overlay ([slots]
    int32 cursor vectors — the vector-`kv_pos` path in
    `nn/layers/attention.py` / `nn/layers/feedforward.py`), so sequences
    at DIFFERENT depths decode in the same single dispatch and a finished
    slot is recycled at the next step boundary:

    - `prefill(ids, pad_to)` runs one prompt through a fresh batch-1
      forward (right-padded to `pad_to`, a warmable shape bucket) and
      returns the next-token distribution plus the slot's primed cache;
    - `install(slot, slot_state, length)` scatters that cache into the
      batched overlay;
    - `step(tokens)` advances ALL slots one token in one jitted dispatch
      ([slots, V] distributions out); free slots ride along on a dummy
      token and are masked by their own cursors;
    - `clear(slot)` retires a sequence (cursor back to 0; its stale cache
      rows are never attended and are overwritten by the next occupant).

    Both entry points go through `cg._get_jit`, so every shape is served
    from (and warmed into) the AOT executable store like any other
    program.
    """

    def __init__(self, cg, slots: int, context=None):
        import jax

        if slots < 1:
            raise ValueError("need at least one decode slot")
        self.cg = cg
        self.slots = int(slots)
        self.capacity = decode_cache_capacity(cg)
        self._declared = cg._declared_state()
        self._state = None  # batched rnn overlay; allocated on first install
        self._rng0 = jax.random.PRNGKey(0)
        # Tensor-parallel serving (`PERF.md §28`): a ParallelContext whose
        # model axis the caller already sharded `cg.params_tree` over
        # (`parallel/mesh.shard_params`). Every prefill/step dispatch runs
        # inside it, so the jit cache + AOT fingerprints key the sharded
        # program distinctly and the traced layers see the mesh. The
        # dispatch inputs carry explicit NamedShardings (params from
        # shard_params, KV overlay from `_alloc`), so one decode step
        # compiles to ONE GSPMD program with XLA-inserted collectives.
        self.context = context
        # Multi-tenant serving (serving/scheduler.py): an adapter-merged
        # params tree substituted for `cg.params_tree` on the next
        # dispatches. Params are jit ARGUMENTS, not statics, so swapping
        # trees of the same structure re-uses the compiled program —
        # zero serving-path compiles on adapter switches.
        self.params_override = None

    def set_params(self, params_tree) -> None:
        """Route subsequent prefill/step dispatches through `params_tree`
        (None restores the graph's own params)."""
        self.params_override = params_tree

    def _params(self):
        return (self.cg.params_tree if self.params_override is None
                else self.params_override)

    def _in_context(self):
        """Context manager active around every jitted dispatch: installs
        the stepper's ParallelContext (no-op wrapper when unsharded, so an
        externally-installed context is left alone)."""
        from deeplearning4j_tpu.parallel.context import context_if_any

        return context_if_any(self.context)

    # -- prompt path ------------------------------------------------------

    def prefill(self, ids, pad_to: int = None):
        """Prime one sequence from scratch. `ids` is a 1-D int prompt;
        `pad_to` right-pads the forward to a bucketed length (causal
        attention: the distribution at the last REAL position never sees
        the pad tail, and the tail's stale cache rows sit beyond the
        rewound cursor, masked until overwritten). Returns
        `(probs [V], slot_state, length)`."""
        import numpy as np
        import jax.numpy as jnp
        from deeplearning4j_tpu import observability as _obs
        from deeplearning4j_tpu.nn import rnn_state as rnn_mod

        ids = [int(i) for i in ids]
        n = len(ids)
        if not n:
            raise ValueError("need at least one prompt token")
        pad_to = int(pad_to or n)
        if pad_to < n:
            raise ValueError(f"pad_to ({pad_to}) < prompt length ({n})")
        if pad_to > self.capacity:
            raise ValueError(
                f"prompt bucket {pad_to} (prompt length {n}) exceeds the "
                f"decode cache capacity {self.capacity}")
        x = np.zeros((1, pad_to, 1), np.float32)
        x[0, :n, 0] = ids
        with self._in_context():
            fn = self.cg._get_jit("output", train=False, keep_rnn_state=True)
            args = (self._params(), self.cg.state, [jnp.asarray(x)], None,
                    self._rng0)
            with _obs.tracer.span("serving.enqueue", cat="serving"):
                outs, new_state = fn(*args)
        rnn = rnn_mod.split_rnn_state(new_state, self._declared)
        # Rewind every cursor from pad_to to the real length.
        rnn = {layer: {k: (jnp.int32(n) if jnp.ndim(v) == 0 else v)
                       for k, v in s.items()}
               for layer, s in rnn.items()}
        with _obs.tracer.span("serving.fetch", cat="serving"):
            probs = np.asarray(outs[0])[0, n - 1]
        return probs, rnn, n

    # -- slot management --------------------------------------------------

    def _alloc(self, template):
        import jax.numpy as jnp

        self._state = {
            layer: {k: jnp.zeros((self.slots,), jnp.int32)
                    if jnp.ndim(v) == 0
                    else jnp.zeros((self.slots,) + v.shape[1:], v.dtype)
                    for k, v in s.items()}
            for layer, s in template.items()
        }

    def install(self, slot: int, slot_state, length: int):
        """Scatter a primed batch-1 cache into the batched overlay."""
        import jax.numpy as jnp

        if self._state is None:
            self._alloc(slot_state)
        for layer, s in slot_state.items():
            dst = self._state[layer]
            for k, v in s.items():
                if jnp.ndim(v) == 0:
                    dst[k] = dst[k].at[slot].set(jnp.int32(length))
                else:
                    dst[k] = dst[k].at[slot].set(v[0])

    def clear(self, slot: int):
        """Retire a slot: cursor to 0 so the next occupant's writes start
        at row 0 and stale rows are never visible."""
        import jax.numpy as jnp

        if self._state is None:
            return
        for s in self._state.values():
            for k, v in s.items():
                if v.ndim == 1 and jnp.issubdtype(v.dtype, jnp.integer):
                    s[k] = v.at[slot].set(0)

    def warm_page_copies(self):
        """Compile any lazily-dispatched page-maintenance ops before
        traffic. The dense stepper has none; the paged stepper overrides
        this with a self-copy that traces the CoW append path."""

    # -- decode path ------------------------------------------------------

    def _before_dispatch(self, t: int):
        """Hook run before every decode dispatch with the step width.
        The paged stepper allocates/CoWs pool pages here."""

    def _dispatch(self, x):
        """One jitted decode dispatch: x is [slots, T, 1] token ids.
        Returns [slots, T, V] distributions (one per fed token)."""
        import numpy as np
        import jax.numpy as jnp
        from deeplearning4j_tpu import observability as _obs
        from deeplearning4j_tpu.nn import rnn_state as rnn_mod

        if self._state is None:
            raise RuntimeError("no sequence installed; call prefill/install")
        with self._in_context():
            fn = self.cg._get_jit("output", train=False, keep_rnn_state=True)
            state = rnn_mod.merge_rnn_state(self.cg.state, self._state)
            args = (self._params(), state, [jnp.asarray(x)], None,
                    self._rng0)
            with _obs.tracer.span("serving.enqueue", cat="serving"):
                outs, new_state = fn(*args)
        self._state = rnn_mod.split_rnn_state(new_state, self._declared)
        # The wait for the device and the copy of the distributions to the
        # host, as one: `np.asarray` does not tell them apart.
        with _obs.tracer.span("serving.fetch", cat="serving"):
            out = np.asarray(outs[0])
        return out if out.ndim == 3 else out[:, None, :]

    def step(self, tokens):
        """Advance every slot one token. `tokens` is [slots] ints (free
        slots take any dummy value). Returns [slots, V] next-token
        distributions."""
        import numpy as np

        x = np.asarray(tokens, np.float32).reshape(self.slots, 1, 1)
        self._before_dispatch(1)
        return self._dispatch(x)[:, -1]

    def step_k(self, tokens):
        """Advance every slot T tokens in ONE dispatch — the speculative
        verify shape: `tokens` is [slots, T] ints and the return is
        [slots, T, V], the distribution AFTER each fed token (row j is
        conditioned on tokens[:, :j+1]). Rows whose later tokens turn out
        wrong are discarded by `rewind_all`; their cache rows sit beyond
        the rewound cursor, masked until overwritten."""
        import numpy as np

        tok = np.asarray(tokens)
        if tok.ndim != 2 or tok.shape[0] != self.slots:
            raise ValueError(
                f"tokens must be [slots={self.slots}, T]; got {tok.shape}")
        x = tok.astype(np.float32)[:, :, None]
        self._before_dispatch(tok.shape[1])
        return self._dispatch(x)

    def rewind_all(self, lengths):
        """Set EVERY slot's cursors (KV + positional) to `lengths[slot]`
        in one batched update per layer — the speculative-decoding
        truncation after a verify step: rejected rows stay in the cache
        beyond the cursor, masked until the next append overwrites them."""
        import numpy as np
        import jax.numpy as jnp

        if self._state is None:
            return
        cur = jnp.asarray(np.asarray(lengths, np.int32).reshape(self.slots))
        for s in self._state.values():
            for k, v in s.items():
                if v.ndim == 1 and jnp.issubdtype(v.dtype, jnp.integer):
                    s[k] = cur


class PagedDecodeStepper(DecodeStepper):
    """`DecodeStepper` over a paged KV pool (vLLM-style PagedAttention).

    Same contract as the dense stepper — `prefill` / `install` / `step` /
    `step_k` / `clear` — but the per-slot [capacity] KV rows are replaced
    by fixed-size pages from one shared `models.kv_pool.KVPagePool`:

    - every attention layer's overlay holds `k_pages`/`v_pages`
      ([pages, page_size, H, D]) plus the [slots] `kv_pos` cursors; the
      int32 page table ([slots, pages_per_seq], host-authoritative in the
      pool) is shipped as ONE device array shared by all layers before
      each dispatch;
    - `install` allocates pages for the prefilled prompt and scatters the
      dense batch-1 cache into them (the prefill program itself is
      unchanged — same warmable buckets);
    - `install_shared` points a slot at already-resident pages (prefix
      cache hit): +1 ref per page, cursor writes only, zero dispatches;
    - `_before_dispatch` advances the pool (page allocation + CoW of
      shared pages in the write range) and applies the planned page
      copies on device, so the in-jit scatter never collides.

    HBM: dense pins `slots * capacity` rows/layer; the pool holds
    `pages * page_size` rows/layer where shared prefixes are resident
    ONCE — the bench's slots-at-equal-HBM multiplier.
    """

    def __init__(self, cg, slots: int, page_size: int = 64,
                 pages: int = None, context=None):
        from deeplearning4j_tpu.models.kv_pool import KVPagePool

        super().__init__(cg, slots, context=context)
        self.pool = KVPagePool(slots=self.slots, capacity=self.capacity,
                               page_size=page_size, pages=pages)
        self.page_size = self.pool.page_size
        self._attn_layers = None  # discovered from the first template
        # Folded into the AOT fingerprint document
        # (compilation/store.py::build_fingerprint_doc) so warmup ships
        # the real paged program, never a dense-geometry executable.
        cg._decode_pool_geometry = {
            "kv": "paged", "page_size": self.page_size,
            "pages": self.pool.num_pages, "slots": self.slots,
        }

    def _page_sharding(self, n_heads: int):
        """NamedSharding for `[pages, page_size, H, Dh]` storage under the
        stepper's context, or None when unsharded (no context/model axis,
        or heads don't divide the axis — then pages replicate, exactly
        like the misaligned layer's params)."""
        ctx = self.context
        if ctx is None or ctx.model_axis is None:
            return None
        n = ctx.axis_size("model")
        if n <= 1 or n_heads % n:
            return None
        from deeplearning4j_tpu.parallel import mesh as mesh_mod

        return mesh_mod.kv_page_sharding(ctx.mesh, 4, ctx.model_axis)

    def _alloc(self, template):
        import jax
        import jax.numpy as jnp

        page, P = self.page_size, self.pool.num_pages
        self._state, self._attn_layers = {}, []
        repl = None
        if self.context is not None:
            from deeplearning4j_tpu.parallel import mesh as mesh_mod

            repl = mesh_mod.replicated(self.context.mesh)

        def put(a, sharding):
            # Explicit placement is the GSPMD in-spec: page storage
            # partitions on the head dim, cursors/tables replicate, and
            # the jitted step inherits the layout (computation follows
            # data). Unsharded steppers keep plain uncommitted arrays.
            if sharding is not None:
                return jax.device_put(a, sharding)
            return a if repl is None else jax.device_put(a, repl)

        for layer, s in template.items():
            if "k_cache" in s:
                k, v = s["k_cache"], s["v_cache"]
                ps = self._page_sharding(k.shape[2])
                self._state[layer] = {
                    "k_pages": put(
                        jnp.zeros((P, page) + k.shape[2:], k.dtype), ps),
                    "v_pages": put(
                        jnp.zeros((P, page) + v.shape[2:], v.dtype), ps),
                    "kv_pos": put(
                        jnp.zeros((self.slots,), jnp.int32), None),
                }
                self._attn_layers.append(layer)
            else:
                self._state[layer] = {
                    kk: put(jnp.zeros((self.slots,), jnp.int32), None)
                    if jnp.ndim(vv) == 0
                    else put(jnp.zeros((self.slots,) + vv.shape[1:],
                                       vv.dtype), None)
                    for kk, vv in s.items()
                }

    def install(self, slot: int, slot_state, length: int):
        """Allocate pages for a freshly-prefilled prompt and scatter its
        dense batch-1 cache into them. The tail page's rows beyond
        `length` carry prefill-pad garbage — masked until overwritten.

        The scatter always covers the slot's WHOLE table row: entries past
        the prompt are 0, the sink page every free slot already writes to,
        so one gather/scatter shape serves every prompt length. Scattering
        only the allocated pages compiled ten eager programs per distinct
        page count — after warm-up, under traffic."""
        import jax.numpy as jnp

        if self._state is None:
            self._alloc(slot_state)
        self.pool.install_slot(slot, length)
        idx = jnp.asarray(self.pool.table[slot])
        page, npg = self.page_size, self.pool.pages_per_seq
        for layer, s in slot_state.items():
            dst = self._state[layer]
            if "k_cache" in s:
                for src_k, dst_k in (("k_cache", "k_pages"),
                                     ("v_cache", "v_pages")):
                    blk = s[src_k][0, :npg * page].reshape(
                        (npg, page) + s[src_k].shape[2:])
                    dst[dst_k] = dst[dst_k].at[idx].set(blk)
                dst["kv_pos"] = dst["kv_pos"].at[slot].set(jnp.int32(length))
            else:
                for kk, vv in s.items():
                    if jnp.ndim(vv) == 0:
                        dst[kk] = dst[kk].at[slot].set(jnp.int32(length))
                    else:
                        dst[kk] = dst[kk].at[slot].set(vv[0])

    def install_shared(self, slot: int, pages, length: int):
        """Prefix-cache hit: point `slot` at resident pages (+1 ref each)
        and set its cursors — no prefill, no KV writes. The first
        divergent append CoWs the shared tail page (refcount >= 2)."""
        import jax.numpy as jnp

        if self._state is None:
            raise RuntimeError(
                "no paged state allocated yet; the first prompt must go "
                "through prefill/install")
        self.pool.install_shared(slot, pages, length)
        for s in self._state.values():
            for kk, vv in s.items():
                if vv.ndim == 1 and jnp.issubdtype(vv.dtype, jnp.integer):
                    s[kk] = vv.at[slot].set(jnp.int32(length))

    def clear(self, slot: int):
        self.pool.free_slot(slot)
        super().clear(slot)

    def warm_page_copies(self):
        """Trace the CoW page copy (`k_pages[src]` gather + `.at[dst]`
        scatter) with a page-0 self-copy. A prefix-cache hit's first
        divergent append runs these exact eager ops in `_before_dispatch`;
        without this they compile mid-decode on the first shared-page
        write, which breaks the zero-compiles-after-warmup guarantee."""
        import numpy as np
        import jax.numpy as jnp

        if self._state is None:
            return
        idx = jnp.asarray(np.asarray([0], np.int32))
        for layer in self._attn_layers:
            s = self._state[layer]
            s["k_pages"] = s["k_pages"].at[idx].set(s["k_pages"][idx])
            s["v_pages"] = s["v_pages"].at[idx].set(s["v_pages"][idx])

    def rewind_all(self, lengths):
        import numpy as np

        for slot, n in enumerate(np.asarray(lengths).reshape(self.slots)):
            self.pool.rewind(slot, int(n))
        super().rewind_all(lengths)

    def _before_dispatch(self, t: int):
        """Advance the pool by `t` tokens for every tracked slot, apply
        the planned CoW page copies on device, and refresh the device
        page table (one array shared by every attention layer)."""
        import numpy as np
        import jax.numpy as jnp

        copies = self.pool.plan_appends(t)
        # One width-1 copy per CoW'd page, not one width-N batch: how many
        # slots diverge in the same round is scheduling-dependent, and each
        # distinct N would trace a fresh gather/scatter shape mid-decode.
        # Width 1 reuses the program `warm_page_copies` compiled.
        for src_page, dst_page in copies:
            src = jnp.asarray(np.asarray([src_page], np.int32))
            dst = jnp.asarray(np.asarray([dst_page], np.int32))
            for layer in self._attn_layers:
                s = self._state[layer]
                s["k_pages"] = s["k_pages"].at[dst].set(s["k_pages"][src])
                s["v_pages"] = s["v_pages"].at[dst].set(s["v_pages"][src])
        pt = jnp.asarray(self.pool.table)
        if self.context is not None:
            import jax

            from deeplearning4j_tpu.parallel import mesh as mesh_mod

            # Host-authoritative table, replicated on every chip: the
            # paged gather/scatter indexes it shard-locally.
            pt = jax.device_put(pt, mesh_mod.replicated(self.context.mesh))
        for layer in self._attn_layers:
            self._state[layer]["page_table"] = pt
