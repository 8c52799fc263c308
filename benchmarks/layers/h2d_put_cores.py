"""Cores the process kept busy while the put ran: ``proc_cpu_s`` of the
``replay.h2d`` spans (every thread's CPU seconds: the caller's, the runtime's
transfer threads', a mesh's upload threads') less their ``replay.h2d.bucket``
children's own thread's, over the seconds of their ``replay.h2d.put``
children. Near 0 the host waits on the link; near 1 (4 on the mesh) it
copies; above, the runtime's threads work beside the caller."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.put_cores(run)
