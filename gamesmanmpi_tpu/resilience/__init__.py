"""resilience: deterministic fault injection, transient retry, watchdog.

The runs this system exists for — multi-hour retrograde sweeps over
billions of positions (the Pentago in-core solve, the weeks-long
computation behind "Othello is Solved") — outlast the time between
host or device failures, and the serving layer must degrade instead of
dying under reader faults. Three pieces, one subsystem:

* ``faults`` — a deterministic fault-injection registry: named fault
  points woven into checkpoint save/load, engine level steps, the
  sharded collectives, the DB probe and the batcher flush, armed via
  ``GAMESMAN_FAULTS="point:kind:when"``. Every schedule is replayable
  (occurrence-indexed or seeded), and a disarmed point costs one dict
  lookup.
* ``retry`` — transient-vs-fatal classification of runtime errors plus
  ``retry_call``, the bounded exponential-backoff wrapper the engines
  put around each level's forward/dedup/backward step. Re-entry is from
  the level's checkpoint-consistent inputs (idempotent thanks to the
  atomic ``_savez``), so an absorbed transient is invisible in the
  solved tables and visible in ``gamesman_retries_total``.
* ``supervisor`` — a per-level watchdog whose deadline derives from
  recent level times: when progress stalls past it, thread stacks and
  the last known progress are dumped and the process aborts with the
  checkpoint prefix intact — turning the heartbeat's "observed wedge"
  into a recoverable abort.

Above them (ISSUE 12) sit the campaign pieces:

* ``preempt`` — preemption grace: SIGTERM/SIGUSR1 drain the solve to
  the next level boundary (rank-coordinated in the sharded engine) and
  exit 75 resumable, with a hard deadline behind it.
* ``campaign`` — the solve-side supervisor (``tools/run_campaign.py``):
  auto-resume with bounded backoff, a no-progress breaker with a
  diagnosis bundle, disk-budget GC-and-retry, and an append-only
  ``campaign.jsonl`` ledger. docs/DISTRIBUTED.md "Campaigns".

The capstone test, ``tests/test_resilience.py``, kills a solve at every
registered fault point, resumes it, and asserts byte parity with an
uninterrupted solve; ``tests/test_campaign.py`` does the same one layer
up, to whole campaigns. docs/CONFIG.md lists every knob.
"""

from gamesmanmpi_tpu.resilience.faults import (
    FatalFault,
    FaultError,
    TransientFault,
    clear,
    configure,
    fire,
    known_points,
)
from gamesmanmpi_tpu.resilience.preempt import (
    GRACE_EXIT_CODE,
    PreemptionRequested,
)
from gamesmanmpi_tpu.resilience.retry import is_transient, retry_call
from gamesmanmpi_tpu.resilience.supervisor import Watchdog, maybe_watchdog

__all__ = [
    "GRACE_EXIT_CODE",
    "PreemptionRequested",
    "FaultError",
    "TransientFault",
    "FatalFault",
    "configure",
    "clear",
    "fire",
    "known_points",
    "is_transient",
    "retry_call",
    "Watchdog",
    "maybe_watchdog",
]
