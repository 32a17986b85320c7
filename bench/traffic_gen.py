"""The one traffic generator: a traffic file's parameters -> a Schedule.

A traffic file (``bench/traffic/<name>.json``) gives the server's quorum
(``trigger.active_frac``: the round closes on the fastest share of the
fleet, FixedQuorum and FastestSelection) and the fleet's ``DelayModel``;
this module turns them into the program's ``build_schedule`` inputs,
seeded from the run's seed, streamed one round at a time.  Every seed
gives a schedule of the same shape (the quorum fixes the deliveries per
round); only who delivers, and when, changes.
"""
from __future__ import annotations

from typing import Dict

from repro.core.async_engine import DelayModel
from repro.core.schedule import QuorumTrigger, Schedule, build_schedule


def trigger(spec: Dict) -> QuorumTrigger:
    if spec["kind"] != "quorum":
        raise ValueError(f"unknown trigger kind {spec['kind']!r}")
    return QuorumTrigger(active_frac=spec["active_frac"])


def schedule(traffic: Dict, n_clients: int, seed: int,
             n_rounds: int) -> Schedule:
    """Build ``n_rounds`` rounds of the traffic for a fleet of
    ``n_clients``.  Streamed builds are prefix-stable: a longer horizon
    only appends rounds."""
    delays = DelayModel(n_clients=n_clients, seed=seed, **traffic["delays"])
    return build_schedule(n_rounds, delays, trigger(traffic["trigger"]),
                          stream=True)
