"""Scheduler: the 95th percentile of every gap between consecutive streamed
tokens that end in the window. A step runs every admission launch queued
before it decodes, so the tail is the steps that carry admissions. Which
arrivals share a step depends on their order, so the percentile moves between
the plateau of one [4, 128] launch and that of two launches or a [4, 256]
one from seed to seed: too widely for a bound, so it stands here, unbounded."""

import numpy as np


def read(rec):
    if not rec["itl_s"]:
        return None
    return 1e3 * float(np.percentile(np.asarray(rec["itl_s"], np.float64), 95))
