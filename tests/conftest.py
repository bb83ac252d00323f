"""Test environment: CPU backend faking an 8-device mesh.

SURVEY.md §4.2 axis 2: shard-count invariance is the TPU analog of the
reference's `mpirun -np 1` vs `-np 8`, and the fake-backend mechanism for CI
is XLA's host-platform device-count flag.

The tests run with JAX_PLATFORMS=cpu; utils/platform.force_platform pins
the CPU backend in-process as well and fakes the 8 devices, before the
first backend use. The persistent compile cache stays off: the CLI would
otherwise place it in the repo (utils/platform.use_compile_cache), and CPU
test programs have no business there.
"""

import jax

from gamesmanmpi_tpu.utils.platform import force_platform

force_platform("cpu", fake_devices=8)
jax.config.update("jax_enable_compilation_cache", False)

# The dense engine's cross-process reachable-count cache must not satisfy
# the parity tests from a previous run's file — counts there must come
# from a real sweep regardless of the invoking shell's env (the file path
# itself is covered by a dedicated test, which monkeypatches this).
import os  # noqa: E402

os.environ["GAMESMAN_DENSE_COUNTS_FILE"] = "0"

# Runtime lock-order witness (docs/ANALYSIS.md "lockdep"): under
# GAMESMAN_LOCKDEP=1 every obs/serve/resilience lock records its
# acquisition edges, and a witnessed lock-order cycle fails the run at
# session teardown — the dynamic validation of the GM2xx/GM6xx static
# lock model.
from gamesmanmpi_tpu.analysis import lockdep  # noqa: E402

if lockdep.enabled_by_env():
    lockdep.install()

# Runtime wire-conformance witness (docs/ANALYSIS.md "wirecheck"):
# under GAMESMAN_WIRECHECK=1 every live response from a watched fleet
# handler is checked against the statically extracted GM10xx contract
# (status codes, Retry-After/Cache-Control/traceparent rules), and a
# violation fails the run at session teardown.
from gamesmanmpi_tpu.analysis import wirecheck  # noqa: E402

if wirecheck.enabled_by_env():
    wirecheck.install()


def pytest_sessionfinish(session, exitstatus):
    if lockdep.enabled_by_env():
        try:
            lockdep.assert_acyclic()
        except lockdep.LockOrderError as e:
            import sys

            print(f"\nGAMESMAN_LOCKDEP: {e}", file=sys.stderr)
            session.exitstatus = 3
    if wirecheck.enabled_by_env():
        try:
            wirecheck.assert_conformant()
        except wirecheck.WireConformanceError as e:
            import sys

            print(f"\nGAMESMAN_WIRECHECK: {e}", file=sys.stderr)
            session.exitstatus = 3
