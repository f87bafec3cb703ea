"""The Nemotron cell's scopes: ``hlo_scopes.scope_seconds`` with an order of
this cell's own, and the scan's share of its roofline.

models/nemotron_h.py traces a layer under the scope of its one mixer: ``ssm``
(a Mamba-2 layer: norm, in-projection, convolution, gate, group norm,
out-projection, residual) with ``ssm_scan`` inside it around the scan
(ops/ssm.py), ``attn_full`` (the attention layer), and the expert layer's
``moe_route``, ``moe_experts`` (parallel/moe.py, as the other decoders'),
``moe_latent`` (the two latent projections) and ``moe_shared`` (the shared
expert, the layer's norm and its sums).
"""

from __future__ import annotations

from . import hlo_scopes

#: in the order of ``hlo_scopes``'s rule: an instruction belongs to the
#: first of these its ``op_name`` holds (``ssm_scan`` lies inside ``ssm``
#: and so comes before it; ``forward_backward`` holds the whole model's
#: pass and so comes last)
SCOPES = ("ssm_scan", "ssm", "attn_full", "moe_latent", "moe_route",
          "moe_experts", "moe_shared", "head_loss", "embed", "update",
          "exchange", "augment", "forward_backward")


def scope_ms(run, *scopes: str):
    """Device milliseconds a step under ``scopes`` of ``SCOPES`` (their
    sum) in the kept ``jit_worker_step``; ``None`` when there is nothing to
    read: no trace, a driver that kept no text, or a program that has none
    of the scopes. The first reader of a run logs the whole table and what
    its catch-all rows are made of."""
    if "nemotron_scopes" not in run.__dict__:
        run.nemotron_scopes = hlo_scopes.scope_seconds(
            run, "jit_worker_step", SCOPES, hlo_scopes.STEP_BY_INSTRUCTION)
        if run.nemotron_scopes is not None:
            found, kinds = run.nemotron_scopes
            rows = sorted(found.items(), key=lambda kv: -kv[1])
            print(f"[bench] device ms a step by scope (median step "
                  f"{run.trace.step_device_ms():.3f}): "
                  + ", ".join(f"{name} {1e3 * seconds:.3f}"
                              for name, seconds in rows)
                  + f"; sum {1e3 * sum(found.values()):.3f}", flush=True)
            for rest in ("ssm_scan", "ssm", "moe_route", "forward_backward",
                         "other"):
                top = sorted(kinds.get(rest, {}).items(),
                             key=lambda kv: -kv[1])[:8]
                print(f"[bench] {rest} is: " + ", ".join(
                    f"{kind} {1e3 * seconds:.3f}" for kind, seconds in top),
                    flush=True)
    if run.nemotron_scopes is None:
        return None
    found, _kinds = run.nemotron_scopes
    if not any(scope in found for scope in scopes):
        return None
    return 1e3 * sum(found.get(scope, 0.0) for scope in scopes)


def scan_roofline(run):
    """Percent of its roofline at which a step's state-space scans ran: the
    least time the chip could take for what the mathematics needs (the
    operations at the bf16 peak or the bytes at the HBM bandwidth,
    whichever is longer; ``ops_count/<family>.py:ssm_scan_cost``, from the
    shapes alone) over the device time under the ``ssm_scan`` scope,
    forward, recomputation and backward. The same work whatever implements
    the scan. ``None`` without a trace, the scope or the cost."""
    ms = scope_ms(run, "ssm_scan")
    cost = getattr(run.cell._ops_count(), "ssm_scan_cost", None)
    if not ms or cost is None:
        return None
    operations, nbytes = cost(run.cell.config["architecture"],
                              run.images_per_device_step)
    by_compute = operations / run.peak["bf16_flops_per_s"]
    by_memory = nbytes / run.peak["hbm_bytes_per_s"]
    print(f"[bench] ssm_scan: {ms:.3f} ms a step; a step's scans need "
          f"{operations / 1e9:.1f} GFLOP ({1e3 * by_compute:.3f} ms at "
          f"peak) and {nbytes / 1e6:.1f} MB ({1e3 * by_memory:.3f} ms), "
          f"bound by {'compute' if by_compute >= by_memory else 'memory'}",
          flush=True)
    return 100.0 * max(by_compute, by_memory) / (ms / 1e3)
