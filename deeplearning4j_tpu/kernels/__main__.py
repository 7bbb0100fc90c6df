"""``python -m deeplearning4j_tpu.kernels`` — kernel resolution report.

Prints, for every registered kernel, the active mode (and which env knob
set it), the implementation that resolves on this process's backend at a
generic signature, and the availability reason. ``--json`` emits the
same rows as a JSON list.

``--probe KERNEL SHAPES DTYPES`` dry-runs a hypothetical signature
instead: every candidate's availability and refusal reason is printed
(no jit required), for debugging forced-kernel rollouts — e.g.::

    python -m deeplearning4j_tpu.kernels --probe bottleneck_block \\
        256,56,56,64,64,256,1,1 float32 --meta train=true --meta act=relu

SHAPES is a comma-separated int tuple (the kernel's registry signature
order), DTYPES a comma-separated dtype list, and repeatable
``--meta key=value`` pairs fill the meta tuple (``true``/``false``
parse to booleans, digits to ints). A signature of several shapes
(`fused_update`'s: one a leaf) separates them with ``;`` (a single leaf
ends in one). With ``--backend tpu`` the answers are the ones a TPU
process would get, from any machine::

    python -m deeplearning4j_tpu.kernels --backend tpu --probe \
        flash_attention_paged 8,1,8,64,129,64,16 float32 \
        --meta mesh_devices=4
    python -m deeplearning4j_tpu.kernels --backend tpu --probe \
        fused_update "2048,128;16,2048,768" float32 --meta kind=adam
    python -m deeplearning4j_tpu.kernels --backend tpu --probe \
        grouped_matmul 131072,2304,896,8 bfloat16,bfloat16 \
        --meta entry=contracted
    python -m deeplearning4j_tpu.kernels --backend tpu --probe \
        rotary 16384,4096,128 bfloat16
"""

from __future__ import annotations

import argparse
import json
import sys


def _parse_meta(pairs):
    meta = []
    for p in pairs or ():
        k, _, v = p.partition("=")
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        elif v.isdigit():
            v = int(v)
        meta.append((k, v))
    return tuple(meta)


def _parse_shapes(text):
    def ints(part):
        return tuple(int(d) for d in part.split(",") if d)

    if ";" in text:  # one shape a leaf: `fused_update`'s signature
        return tuple(ints(part) for part in text.split(";") if part)
    return ints(text)


def _probe(args) -> int:
    from deeplearning4j_tpu.kernels import registry

    kernel, shapes_s, dtypes_s = args.probe
    shapes = _parse_shapes(shapes_s)
    dtypes = tuple(d for d in dtypes_s.split(",") if d)
    meta = _parse_meta(args.meta)
    selected, rows = registry.probe(kernel, backend=args.backend,
                                    shapes=shapes, dtypes=dtypes, meta=meta)
    mode, source = registry.mode_for(kernel)
    if args.json:
        print(json.dumps({"kernel": kernel, "mode": mode,
                          "mode_source": source, "selected": selected,
                          "candidates": rows}, indent=2))
        return 0
    import jax

    backend = args.backend or jax.default_backend()
    msrc = mode if source == "default" else f"{mode} [{source}]"
    print(f"{kernel} on backend={backend} mode={msrc} "
          f"shapes={shapes} dtypes={dtypes} meta={dict(meta)}:")
    for r in rows:
        mark = "-> " if r["impl"] == selected else "   "
        avail = "available" if r["available"] else "unavailable"
        forced = " (probed as forced)" if r["forced"] else ""
        print(f"  {mark}{r['impl']:<6} {avail:<11} {r['reason']}{forced}")
    print(f"resolves: {selected}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.kernels",
        description="List kernel-registry resolutions (and why).")
    ap.add_argument("--json", action="store_true", help="emit JSON rows")
    ap.add_argument("--backend", default=None,
                    help="probe as this backend (default: the process's "
                         "jax.default_backend())")
    ap.add_argument("--probe", nargs=3, default=None,
                    metavar=("KERNEL", "SHAPES", "DTYPES"),
                    help="dry-run one kernel at a hypothetical signature: "
                         "comma-separated SHAPES ints and DTYPES names; "
                         "prints per-candidate availability + reason")
    ap.add_argument("--meta", action="append", default=None,
                    metavar="KEY=VALUE",
                    help="meta entries for --probe (repeatable; "
                         "true/false parse to booleans, digits to ints)")
    args = ap.parse_args(argv)

    if args.probe:
        return _probe(args)

    from deeplearning4j_tpu.kernels import registry

    rows = registry.describe(backend=args.backend)
    if args.json:
        print(json.dumps(rows, indent=2))
        return 0
    import jax

    backend = args.backend or jax.default_backend()
    print(f"kernel registry on backend={backend} "
          f"(DL4J_TPU_KERNELS + per-kernel DL4J_TPU_KERNEL_<NAME>):")
    w = max(len(r["kernel"]) for r in rows)
    for r in rows:
        mode = r["mode"] if r["mode_source"] == "default" else (
            f"{r['mode']} [{r['mode_source']}]")
        print(f"  {r['kernel']:<{w}}  mode={mode:<10} -> {r['impl']:<6} "
              f"{r['reason']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
