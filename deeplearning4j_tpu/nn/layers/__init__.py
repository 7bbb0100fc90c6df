"""Layer implementation registry.

Maps a layer-config class name to its pure apply function — the TPU analog of
the reference's conf->impl instantiation (`conf/layers/*.instantiate()`);
there is no helper SPI because XLA lowers everything (SURVEY.md §7).

Uniform signature:
    apply(conf, params, state, x, *, rng, train, mask) -> (out, new_state, out_mask)
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.layers import (
    attention,
    bottleneck,
    convolution,
    feedforward,
    moe,
    normalization,
    pooling,
    recurrent,
    variational,
)

LAYER_IMPLS = {
    "DenseLayer": feedforward.dense_apply,
    "GatedDenseLayer": feedforward.gated_dense_apply,
    "OutputLayer": feedforward.preoutput,  # loss fused at the network level
    "RnnOutputLayer": feedforward.preoutput,
    "CenterLossOutputLayer": feedforward.preoutput,
    "LossLayer": lambda conf, params, state, x, **kw: (x, state, kw.get("mask")),
    "ActivationLayer": feedforward.activation_apply,
    "DropoutLayer": feedforward.dropout_apply,
    "EmbeddingLayer": feedforward.embedding_apply,
    "AutoEncoder": feedforward.autoencoder_apply,
    "RBM": feedforward.rbm_apply,
    "ConvolutionLayer": convolution.conv2d_apply,
    "BottleneckBlock": bottleneck.bottleneck_apply,
    "SubsamplingLayer": convolution.subsampling_apply,
    "LocalResponseNormalization": convolution.lrn_apply,
    "BatchNormalization": normalization.batchnorm_apply,
    "LayerNormalization": normalization.layernorm_apply,
    "RMSNormalization": normalization.rmsnorm_apply,
    "PositionalEmbeddingLayer": feedforward.positional_embedding_apply,
    "GravesLSTM": recurrent.graves_lstm_apply,
    "LSTM": recurrent.standard_lstm_apply,
    "GravesBidirectionalLSTM": recurrent.bidirectional_lstm_apply,
    "SimpleRnn": recurrent.simple_rnn_apply,
    "GlobalPoolingLayer": pooling.global_pooling_apply,
    "SelfAttentionLayer": attention.self_attention_apply,
    "MoELayer": moe.moe_apply,
    "VariationalAutoencoder": variational.vae_apply,
}

# Layers whose forward emits a *pre-activation* that the network turns into a
# loss (the reference's BaseOutputLayer family).
OUTPUT_LAYER_TYPES = {
    "OutputLayer", "RnnOutputLayer", "LossLayer", "CenterLossOutputLayer",
}

# Layerwise-pretrainable layers (reference: pretrain() RBM/AE/VAE path).
PRETRAIN_LOSSES = {
    "VariationalAutoencoder": variational.vae_pretrain_loss,
    "AutoEncoder": feedforward.autoencoder_pretrain_loss,
    "RBM": feedforward.rbm_pretrain_loss,
}


def get_impl(conf):
    name = type(conf).__name__
    impl = LAYER_IMPLS.get(name)
    if impl is None:
        raise ValueError(f"No implementation registered for layer type {name}")
    return impl
