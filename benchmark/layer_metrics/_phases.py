"""Shared by the phase readers: the workers' exclusive host-clock
seconds per phase (`PhaseTimers`, shipped every 2 s by
`ReportPhaseStats`), as `GetSchedStats.phases.fractions` gives them: a
share of the fleet's last 30 s, summed over workers. The last poll of
the window is read, so the 30 s lie inside it."""


def share(run, names):
    for snap in reversed(run["snaps"]):
        fractions = snap["fractions"]
        if fractions:
            return 100.0 * sum(fractions.get(n, 0.0) for n in names)
    return None
