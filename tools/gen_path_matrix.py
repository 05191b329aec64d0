"""Generate the docs/paths.md support matrix from the serving dispatch.

The matrix is DERIVED, not hand-written, so it cannot drift from the code:

  * ``models/attention.PAGED_DISPATCH`` — the (mechanism, phase) ->
    implementation table the paged attention dispatch actually consults
    (``use_fused``), giving the fused Pallas entry point and the jnp
    gather oracle per cell;
  * ``models/attention.AUTO_GATHER_BACKENDS`` + ``resolve_paged_impl`` —
    the ``paged_impl='auto'`` resolution rule;
  * ``models/transformer.LAYER_KINDS`` — every LM layer kind's mixer:
    its paged cache kind (K/V pages, MLA latent pages, recurrent state
    checkpoints, hybrid composites), the cache key its leaves live under
    and whether it keeps per-slot state;
  * ``serve/engine.EngineConfig`` — which speculative drafters exist and
    what they require (probed by constructing the drafters' gates);
  * ``models/dit.MECHANISM_ATTENTION`` + ``serve/diffusion.ATTN_IMPLS`` —
    the step-level diffusion engine's self-attention dispatch and its
    fused/gather/reference implementation mapping.

The generated tables live between the BEGIN/END markers in docs/paths.md;
everything outside the markers is hand-written prose.

Usage:
    PYTHONPATH=src python tools/gen_path_matrix.py --check   # CI drift gate
    PYTHONPATH=src python tools/gen_path_matrix.py --write   # regenerate
"""
from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, os.path.join(REPO, "src"))

DOC = os.path.join(REPO, "docs", "paths.md")
BEGIN = "<!-- BEGIN GENERATED path-matrix (tools/gen_path_matrix.py) -->"
END = "<!-- END GENERATED path-matrix -->"

# display order / labels; the CELL CONTENT all comes from the dispatch code
MECHANISMS = ("full", "sla2", "sla", "sparse_only")
PHASE_LABEL = {"prefill": "chunked prefill", "decode": "decode",
               "verify": "verify window"}


def generate() -> str:
    """Render the generated section of docs/paths.md as a string."""
    import inspect

    from repro.kernels import ops as K_ops
    from repro.kernels import sla2_decode_paged as KP
    from repro.models import attention as A
    from repro.models import transformer as T

    quant_modes = " / ".join(f"`{m}`" for m in K_ops.KV_QUANT_MODES
                             if m != "none")

    def kv_quant_cell(entry) -> str:
        """Quantized-pool support, probed from the fused entry point's
        actual signature (a ``kv_quant`` parameter means the kernel has
        the dequant-in-kernel path; the gather oracle always follows)."""
        if entry is None:
            return "—"
        fn = getattr(KP, entry[0], None)
        if fn is None:
            return "—"
        try:
            params = inspect.signature(fn).parameters
        except (TypeError, ValueError):
            return "—"
        return quant_modes if "kv_quant" in params else "—"

    from repro.distributed import shard_paged as SP

    def shard_cell(entry) -> str:
        """Sharded-serving axis, probed from the shard_map wrapper table
        (``distributed/shard_paged.ENTRY_AXES``): the mesh axis each
        device's local fused call covers when ``EngineConfig.mesh`` is
        set ('slots' = batched decode rows, 'heads' = prefill KV
        heads)."""
        if entry is None or entry[0] not in SP.ENTRY_AXES:
            return "—"
        return f"`{SP.ENTRY_AXES[entry[0]]}`"

    lines = [BEGIN, ""]

    # --- mechanism x phase x implementation -----------------------------
    lines += [
        "### Mechanism × phase (`ServeEngine`, paged KV pool)",
        "",
        "Derived from `models/attention.PAGED_DISPATCH` — the table the",
        "paged dispatch (`models/attention.use_fused`) consults at runtime.",
        "The `kv_quant` column is probed from the fused entry points'",
        "signatures: listed modes store the page pool low-bit and",
        "dequantize in-kernel (the gather oracle dequantizes the same way).",
        "The `shard` column is probed from "
        "`distributed/shard_paged.ENTRY_AXES`: with `EngineConfig.mesh` "
        "set, the fused entry runs under `shard_map` with that argument "
        "axis split across the mesh (see docs/serving.md §Sharded "
        "serving).",
        "",
        "| mechanism | phase | `paged_impl='fused'` "
        "(Pallas, `kernels/sla2_decode_paged`) | `paged_impl='gather'` "
        "(jnp parity oracle) | `kv_quant` pool | shard |",
        "|---|---|---|---|---|---|",
    ]
    for mech in MECHANISMS:
        for phase in A.PAGED_PHASES:
            entry = A.PAGED_DISPATCH.get((mech, phase))
            if entry is None:
                fused, gather = "—", "—"
            else:
                fused = f"`{entry[0]}`"
                gather = f"`{entry[1]}`"
            lines.append(f"| `{mech}` | {PHASE_LABEL[phase]} | {fused} "
                         f"| {gather} | {kv_quant_cell(entry)} "
                         f"| {shard_cell(entry)} |")
    backends = ", ".join(f"`{b}`" for b in A.AUTO_GATHER_BACKENDS)
    lines += [
        "",
        f"`paged_impl='auto'` (the default) resolves to `'gather'` on the "
        f"{backends} backend(s) — where Pallas runs in interpret mode and "
        "the XLA gather path is the faster proxy — and to `'fused'` "
        "everywhere else (`models/attention.resolve_paged_impl`).",
        "",
    ]

    # --- layer kinds: per-kind paged cache geometry ---------------------
    lines += [
        "### Layer kinds (paged cache geometry)",
        "",
        "Derived from `models/transformer.LAYER_KINDS`, the one table of "
        "layer kinds: every LM layer kind serves through the paged "
        "`ServeEngine` — attention layers page K/V, MLA layers page the "
        "compressed latent, recurrent mixers keep per-slot state "
        "checkpoints behind the same swap/prefix-cache plumbing, hybrids "
        "compose both.",
        "",
        "| layer kind | paged cache kind | cache key | per-slot state |",
        "|---|---|---|---|",
    ]
    for kind, (mixer, _) in T.LAYER_KINDS.items():
        state = "yes" if mixer.slot_state else "sla2 totals only"
        lines.append(
            f"| `{kind}` | {mixer.cache_kind} | `{mixer.key}` | {state} |")

    # --- speculative drafters -------------------------------------------
    # import the drafters so a rename/removal breaks --check loudly
    from repro.serve.speculative import LinearDrafter, NGramDrafter
    drafters = {"linear": LinearDrafter.__name__,
                "ngram": NGramDrafter.__name__}
    lines += [
        "",
        "### Speculative drafters (`EngineConfig.speculative`)",
        "",
        "| mode | drafter | requires | verify pass |",
        "|---|---|---|---|",
        "| `off` | — | — | — (one token per dispatch) |",
        f"| `linear` | `serve/speculative.{drafters['linear']}` | "
        "`mechanism='sla2'` (linear branch) | `sla2_decode_verify` / "
        "gather window |",
        f"| `ngram` | `serve/speculative.{drafters['ngram']}` | any paged "
        "stack | mechanism's verify entry above |",
    ]

    # --- diffusion engine (step-level, no KV cache) ---------------------
    from repro.models import dit as D_dit
    from repro.serve import diffusion as DS
    impl_path = {
        "fused": "Pallas `kernels/sla2_fwd.sparse_flash_fwd` "
                 "(bidirectional, re-routed every denoise step)",
        "gather": "jnp gathered-tiles parity oracle",
        "reference": "O(N²) einsum reference",
    }
    lines += [
        "",
        "### Diffusion engine (`serve/diffusion.DiffusionEngine`, "
        "no KV cache)",
        "",
        "Derived from `models/dit.MECHANISM_ATTENTION` (the per-step "
        "self-attention dispatch) and `serve/diffusion.ATTN_IMPLS` (the "
        "`attn_impl` → `DiTConfig.sla2_impl` mapping). The scheduling "
        "unit is one denoise step; there is no paged pool — a request's "
        "footprint is one constant batch slot.",
        "",
        "| `mechanism` | self-attention (`models/dit`) |",
        "|---|---|",
    ]
    for mech, fn in D_dit.MECHANISM_ATTENTION.items():
        lines.append(f"| `{mech}` | `{fn.__name__}` |")
    lines += [
        "",
        "| `attn_impl` | `DiTConfig.sla2_impl` | path |",
        "|---|---|---|",
    ]
    for impl, sla2_impl in DS.ATTN_IMPLS.items():
        lines.append(f"| `{impl}` | `{sla2_impl}` | {impl_path[impl]} |")
    # exercise the resolver so a rename/behaviour change breaks --check
    assert DS.resolve_attn_impl("fused") == "fused"
    lines += [
        "",
        f"`attn_impl='auto'` resolves to `'gather'` on the {backends} "
        "backend(s) and `'fused'` everywhere else "
        "(`serve/diffusion.resolve_attn_impl`, same rule as "
        "`paged_impl='auto'`).",
        "",
        END,
    ]
    return "\n".join(lines)


def splice(text: str, block: str) -> str:
    """Replace the marker-delimited block inside ``text`` with ``block``."""
    i, j = text.index(BEGIN), text.index(END) + len(END)
    return text[:i] + block + text[j:]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when docs/paths.md drifted from the code")
    ap.add_argument("--write", action="store_true",
                    help="regenerate the matrix in docs/paths.md in place")
    args = ap.parse_args()
    block = generate()
    if not os.path.exists(DOC):
        if args.check:
            print(f"ERROR: {os.path.relpath(DOC, REPO)} missing",
                  file=sys.stderr)
            return 1
        raise SystemExit("docs/paths.md does not exist; create its prose "
                         "shell (with the BEGIN/END markers) first")
    current = open(DOC).read()
    if BEGIN not in current or END not in current:
        print("ERROR: docs/paths.md lost its generation markers",
              file=sys.stderr)
        return 1
    updated = splice(current, block)
    if args.check:
        if updated != current:
            print("ERROR: docs/paths.md support matrix drifted from the "
                  "dispatch code — run `PYTHONPATH=src python "
                  "tools/gen_path_matrix.py --write`", file=sys.stderr)
            return 1
        print("docs/paths.md matrix in sync with the dispatch code")
        return 0
    if args.write:
        with open(DOC, "w") as fh:
            fh.write(updated)
        print(f"wrote {os.path.relpath(DOC, REPO)}")
        return 0
    print(block)
    return 0


if __name__ == "__main__":
    sys.exit(main())
