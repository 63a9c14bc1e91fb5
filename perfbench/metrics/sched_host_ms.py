"""Scheduler (host): milliseconds that the host spends in one scheduler
loop iteration outside the blocking device-to-host reads, the median over
the iterations of the traced interval: the scheduler's own
``SchedulerStats.host_s``, which each ``sched.step`` span carries at entry
(``host_us``), differenced from one step to the next.  A decode dispatch
that waits behind a prefill window counts as host time in the counter;
the median keeps those iterations out."""

import spantrace


def read(obs):
    sp = spantrace.of(obs)
    return sp.host_ms_per_iteration() if sp else None
