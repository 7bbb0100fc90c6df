"""ResNet-50 for `fit`: the network, its staged data and its checks.

`build(sizes, seed, chips)` returns what the `fit` driver needs. Everything
that is a size comes from the JSON beside this file.
"""

from __future__ import annotations

source = "He et al. 2015, arXiv:1512.03385, Table 1 (50-layer)"


def build(sizes: dict, seed: int, chips: int) -> dict:
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator)
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    image, classes = int(sizes["image"]), int(sizes["n_classes"])
    batch = int(sizes["batch_per_chip"]) * chips
    conf = resnet50(n_classes=classes, image=image,
                    channels=int(sizes["channels"]),
                    dtype=sizes["param_dtype"],
                    lr=float(sizes["learning_rate"]),
                    seed=seed % (2 ** 31 - 1))
    conf.global_conf.dtype_policy = dict(sizes["dtype_policy"])
    net = ComputationGraph(conf).init()

    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(int(sizes["staged_batches"])):
        x = rng.random((batch, image, image, int(sizes["channels"])),
                       dtype=np.float32)
        y = np.zeros((batch, classes), np.float32)
        y[np.arange(batch), rng.integers(0, classes, batch)] = 1.0
        batches.append(DataSet(x, y))

    if chips > 1:
        from deeplearning4j_tpu.parallel import mesh as mesh_mod
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        trainer = ParallelWrapper(net, mesh=mesh_mod.create_mesh(
            devices=jax.devices()[:chips]))
        iterator = batches  # the wrapper shards each host batch itself
    else:
        trainer = net
        iterator = DeviceCacheDataSetIterator(
            batches, transfer_dtype=net.dtype_policy.transfer_dtype)

    def forward(params, x):
        outs, _, _, _ = net._forward_fn(params, net.state, [x], None, False,
                                        None)
        return outs

    def example_input():
        return jax.ShapeDtypeStruct((1, image, image, int(sizes["channels"])),
                                    jax.numpy.bfloat16)

    return {"net": net, "trainer": trainer, "iterator": iterator,
            "batches": batches, "samples_per_epoch": batch * len(batches),
            "steps_per_epoch": len(batches),
            "forward": forward, "example_input": example_input}
