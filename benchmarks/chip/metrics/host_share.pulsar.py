"""Percent of the client's submit + drain host time spent outside the
service's ``execute`` spans (stacking, padding, receipts, queueing)."""


def read(run):
    return run.host_share()
