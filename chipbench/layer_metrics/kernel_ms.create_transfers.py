"""Device milliseconds per create_transfers dispatch: the union of
the op intervals under each executed XLA module whose name starts with
`jit_create_transfers` (trace_reduce.KERNEL_MODULES), from the
profiler's trace, averaged over the dispatches of the traced window."""


def read(context: dict):
    dev = context["device"]
    if dev is None or not dev["dispatch_seconds"]:
        return None
    d = dev["dispatch_seconds"]
    return 1e3 * sum(d) / len(d)
