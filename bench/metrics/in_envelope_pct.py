"""Share of the answered requests whose list lies within the MED-RBP
envelope tau of the reference's list at the largest cutoff."""


def read(run):
    return run.in_envelope_pct
