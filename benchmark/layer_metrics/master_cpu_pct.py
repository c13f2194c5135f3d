"""CPU the master process used over the window (utime + stime from
`/proc/<pid>/stat`), as a share of one core: 100 = one core busy. The
PS apply, the task dispatcher and the RPC server all live there."""


def read(run):
    used = run["window"]["master_cpu_s"]
    if used is None:
        return None
    return 100.0 * used / run["window"]["window_s"]
