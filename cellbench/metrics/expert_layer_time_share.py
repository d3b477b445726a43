"""Model: device time of an expert layer's MLP, the router, the dispatch,
the routed experts, the combine and the shared expert (ops whose `tf_op`
lies under `moe_route`, `moe_dispatch`, `moe_experts`, `moe_combine` or
`moe_shared`, in any walk and on either dispatch) over device busy time,
traced span. Nothing to read on a program without a shared expert's
scope: the cells of models without one have `moe_expert_time_share`."""
from cellbench import hostplane

SCOPES = ("/moe_route/", "/moe_dispatch/", "/moe_experts/", "/moe_combine/",
          "/moe_shared/")


def read(ctx):
    trace = hostplane.trace_of(ctx)
    plane = hostplane.first_device(trace) if trace else None
    ops = plane.get(hostplane.OPS_LINE, []) if plane else []
    if not any(e[3] and "/moe_shared/" in e[3] for e in ops):
        return None
    busy = hostplane.union_ns(ops)
    hit = [e for e in ops if e[3] and any(s in e[3] for s in SCOPES)]
    return 100.0 * hostplane.union_ns(hit) / busy if busy > 0 else None
