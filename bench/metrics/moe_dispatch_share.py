"""MoE dispatch: device time of the ops under the program's scopes
``moe.route``, ``moe.dispatch`` and ``moe.combine`` over the device time
of module ``jit_bench_moe``, in percent, from passes traced after the
window (each op sorted by the scope its HLO instruction carries)."""


def read(run):
    scopes = run.extra.get("moe_scopes")
    if not scopes or not scopes[1]:
        return None
    scoped_s, module_s = scopes
    return 100.0 * scoped_s / module_s
