"""Share of its roofline the impact_scan kernel reached in the traced
stretch: least time for the work it was given over the time its events
took on the device (``harness.roofline.impact_scan_work``)."""

from harness.roofline import impact_scan_work


def read(run):
    return run.roofline_pct("impact_scan", impact_scan_work)
