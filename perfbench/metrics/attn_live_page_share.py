"""Decode attention kernel: the share of the slots' table entries that the
paged attention kernel visits, over the decode steps and prefill windows
whose scheduler spans (``sched.decode``, ``sched.window``) start inside
the traced interval: the spans' ``live_pages`` summed, over their
``table_pages`` summed, in percent.  Spans without these args (a program
that bounds nothing) read as nothing."""

import spantrace

PROGRAM_SPANS = ("sched.decode", "sched.window")


def read(obs):
    sp = spantrace.of(obs)
    if sp is None:
        return None
    args = [a for name, start, _, a in sp.spans
            if name in PROGRAM_SPANS and sp.t0 <= start <= sp.t1
            and "table_pages" in a]
    table = sum(a["table_pages"] for a in args)
    if not table:
        return None
    return 100.0 * sum(a["live_pages"] for a in args) / table
