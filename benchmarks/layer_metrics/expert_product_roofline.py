"""expert_product_roofline: the grouped expert products' own share of
their bytes bound, in the block-diffusion sentence tower: over the text
flushes of the TRACED window, the bytes of the matrices of every expert
that got a pair (``moe_experts_touched`` of the flush's ``dispatch``
record, summed over layers and passes, x 3 x hidden x width x 2 bytes)
over peak bytes/s, over the device time of the operations whose
``op_name`` holds ``grouped_matmul`` (``ops/grouped_matmul.py``'s three
kernels a turn) inside the window.  A flush counts by the share of its
hold that lies inside the window (``dlm_tower_roofline.flushes_inside``):
the same stretch above and below.  The bytes bound holds (an expert is fed
tens of rows a pass).  The time by scope is the driver's reduction of the
trace (``run.extra["scope_seconds"]``)."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "queries_per_s"

KERNELS = "grouped_matmul"


def read(run):
    from benchmarks import flops_sdar
    from benchmarks.layer_metrics.dlm_tower_roofline import flushes_inside

    scopes = run.extra.get("scope_seconds")
    flushes = flushes_inside(run, "moe_experts_touched")
    if not scopes or not scopes["inside"].get(KERNELS) or not flushes:
        return None
    least_s = (sum(share * e["moe_experts_touched"] for e, share in flushes)
               * flops_sdar.expert_params(run.cell.config) * flops_sdar.BYTES
               / run.peaks["bytes_per_s"])
    return 100.0 * least_s / scopes["inside"][KERNELS]
