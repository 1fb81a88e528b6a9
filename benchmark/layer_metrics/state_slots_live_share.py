"""Percent of the engine's state slots that held a running sequence when a
tick was launched, a traced tick's mean (`state_slots_live` on the step
spans over the configuration's `state_slots`)."""
from benchmark.lib import ssm_math


def read(record):
    return ssm_math.slots_live_share(record)
