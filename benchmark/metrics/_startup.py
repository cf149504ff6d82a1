"""What the set-up metrics of PR 35 share.  Set-up lies BEFORE the
window, so they read ABSOLUTE values in ``run["before"]``, the
registry as the window opens (as ``compile_s`` reads
``run["compile_s"]``), not growth between the two snapshots.  A program
without the names (the parent of PR 35) reads ``None`` everywhere,
never an error."""


def family_at_open(run, family):
    """``{label part: value}`` of every series of ``family`` as the
    window opens (``''`` for a series without labels)."""
    return {key[len(family):]: value
            for key, value in run["before"]["counters"].items()
            if key == family or key.startswith(family + "{")}


def span_seconds_at_open(run, names, needs=None,
                         counter="span_seconds_total"):
    """Seconds of ``counter`` for the spans in ``names`` as the window
    opens; ``None`` when none of them (or not ``needs``, the one that
    only a program with the start-up timeline records) has ended."""
    at_open = family_at_open(run, counter)
    if needs is not None and '{name="%s"}' % needs not in at_open:
        return None
    found = [at_open[label] for label in
             ('{name="%s"}' % n for n in names) if label in at_open]
    return sum(found) if found else None
