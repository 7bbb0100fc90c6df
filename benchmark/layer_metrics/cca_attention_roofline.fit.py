"""kernels: the compressed convolutional attention core's share of the chip's
peak, in percent: the FLOP the core of every layer needs in the traced steps
(9 product passes of 2 x 128 FLOP a pair and query head over the causal
band, 8 query heads, from the configuration's sizes:
`harness/cca_costs.py`) over the time under the scope `cca.attend` times the
chip's peak bf16 FLOP/s. Bound by compute. It divides by the time under the
layers' scope and not by the time in calls of one name, so a relayout beside
the kernel or a tile of masked pairs lowers it, and it cannot pass 100%."""

SCOPES = ("cca.attend",)


def read(context):
    from benchmark.harness import cca_costs

    return cca_costs.roofline_percent(context, SCOPES)
