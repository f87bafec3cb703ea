"""The SmallThinker cell's scopes: ``hlo_scopes.scope_seconds`` with an
order of this cell's own. ``hlo_scopes.STEP_SCOPES`` is the first decoder's
(``mtp``, ``mla``, ...); this model's attention is traced under
``attn_window`` (three layers of four) and ``attn_full`` (the fourth), its
expert layer under the same ``moe_route`` / ``moe_experts`` as the other's.
"""

from __future__ import annotations

from . import hlo_scopes

#: in the order of ``hlo_scopes``'s rule: an instruction belongs to the
#: first of these its ``op_name`` holds (``forward_backward`` holds the
#: whole model's pass and so comes last)
SCOPES = ("attn_window", "attn_full", "moe_route", "moe_experts",
          "head_loss", "embed", "update", "exchange", "augment",
          "forward_backward")


def scope_ms(run, scope: str):
    """Device milliseconds a step under ``scope`` of ``SCOPES`` in the kept
    ``jit_worker_step``; ``None`` when there is nothing to read. The first
    reader of a run logs the whole table and what its catch-all rows are
    made of."""
    if "smallthinker_scopes" not in run.__dict__:
        run.smallthinker_scopes = hlo_scopes.scope_seconds(
            run, "jit_worker_step", SCOPES, hlo_scopes.STEP_BY_INSTRUCTION)
        if run.smallthinker_scopes is not None:
            scopes, kinds = run.smallthinker_scopes
            rows = sorted(scopes.items(), key=lambda kv: -kv[1])
            print(f"[bench] device ms a step by scope (median step "
                  f"{run.trace.step_device_ms():.3f}): "
                  + ", ".join(f"{name} {1e3 * seconds:.3f}"
                              for name, seconds in rows)
                  + f"; sum {1e3 * sum(scopes.values()):.3f}", flush=True)
            for rest in ("attn_window", "attn_full", "forward_backward",
                         "other"):
                top = sorted(kinds.get(rest, {}).items(),
                             key=lambda kv: -kv[1])[:8]
                print(f"[bench] {rest} is: " + ", ".join(
                    f"{kind} {1e3 * seconds:.3f}" for kind, seconds in top),
                    flush=True)
    if run.smallthinker_scopes is None:
        return None
    scopes, _kinds = run.smallthinker_scopes
    return 1e3 * scopes[scope] if scope in scopes else None
