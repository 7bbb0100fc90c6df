"""kernels: the latent attention's share of the chip's peak, in percent: the
FLOP the attention core of every layer needs in the traced steps (2,944 a
pair and head over the causal band at the published widths: five product
passes at 128 + 64 and four at 128, from the configuration's sizes:
`harness/latent_costs.py`) over the time under the scope `mla.attend` times
the chip's peak bf16 FLOP/s. Bound by compute. It divides by the time under
the layers' scope and not by the time in calls of one name, so a relayout
beside the kernel, a tile of masked pairs or an operand padded to a wider
lane count lowers it, and it cannot pass 100%."""

SCOPES = ("mla.attend",)


def read(context):
    from benchmark.harness import latent_costs

    return latent_costs.roofline_percent(context, SCOPES)
