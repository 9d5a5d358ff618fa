"""Share of its roofline the held experts' grouped matmul reaches (%):
the least time the chip needs for the ``moe_gmm`` calls of one update,
the larger of their bytes over HBM bandwidth and their operations over
the bf16 peak (``gmm_bytes_per_update`` and ``gmm_flops_per_update`` of
the cell's configuration module: every call reads the held experts once
and the routed rows at the uniform-routing expectation in and out), over
the kernel's measured time per update (as ``moe_gmm_ms_per_update``
sums it). The context carries no per-cell counts, so the cell is this
metric's own ``workloads`` entry in ``BENCHMARK.json``. Nothing when no
such kernel ran."""
import json
import os

NAME = "moe_gmm_roofline"
KERNEL = "moe_gmm"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(ctx):
    t = sum(s for op, s in ctx.reduced.op_s.items() if KERNEL in op)
    if t <= 0 or ctx.updates == 0:
        return None
    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    cell = harness.load_cell(entry["workloads"][0])
    model, cfg, mix = cell.model, cell.cfg, cell.mix
    least = max(model.gmm_bytes_per_update(cfg, mix)
                / ctx.peak["hbm_bytes_per_s"],
                model.gmm_flops_per_update(cfg, mix) / ctx.peak["bf16_flops"])
    return 100.0 * least * ctx.updates / t
