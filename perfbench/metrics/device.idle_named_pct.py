"""Of the idle seconds the host causes on the first device plane in the
traced window, the share that carries the name of what the host was
doing: it falls when host work appears outside every phase's span.
Each gap between device operations is split among the innermost
program spans it crosses, and the whole table is printed. What falls
inside `worker.wait` is left out of both sides (there the host is
waiting for the device: gaps within the device's own step); the rest
is named unless it lies in the round's root `sched.step` itself or
outside any span."""

from perfbench.sources import program_spanlog

DEVICE_S_OWN = "worker.wait"
UNNAMED = ("sched.step", program_spanlog.OUTSIDE)


def read(run):
    idle = program_spanlog.traced_idle_by_span(run)
    if not idle:
        return None
    run.say("idle by program span: " + ", ".join(
        f"{name} {s:.4f}s" for name, s in
        sorted(idle.items(), key=lambda kv: -kv[1])))
    host = {name: s for name, s in idle.items() if name != DEVICE_S_OWN}
    total = sum(host.values())
    named = sum(s for name, s in host.items() if name not in UNNAMED)
    return 100.0 * named / total if total > 0 else None
