"""Share of the traced stretch in which no operation ran on the chip."""


def read(run):
    return run.device_idle_pct()
