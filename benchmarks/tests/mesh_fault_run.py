"""Drive a CPU rehearsal of a four-chip cell with a fault that only a mesh
can have.

    python3 benchmarks/tests/mesh_fault_run.py FAULT [run.py arguments...]

FAULT is one of:
- ``none``: nothing broken;
- ``lost_peer``: in every backward reply exchange of the sharded engine,
  the block each shard receives from one peer (the next shard round the
  mesh) comes back as the miss cell 0, as if that peer's answers never
  arrived.

The fault goes into ``parallel.sharded`` before any kernel is traced.
``fault_run.py`` holds the faults that a one-chip cell can have too.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1])]


def lose_a_peer(sharded):
    import jax
    import jax.numpy as jnp

    def blank_peer(reply):
        """[S, ...] exchanged blocks, the next shard's block zeroed."""
        S = reply.shape[0]
        peer = (jax.lax.axis_index(sharded.AXIS) + 1) % S
        lost = (jnp.arange(S) == peer).reshape((S,) + (1,) * (reply.ndim - 1))
        return jnp.where(lost, jnp.zeros_like(reply), reply)

    exchange = sharded._all_to_all
    edges_reply = sharded._sharded_edges_reply_step
    reply_core = sharded._reply_core

    def lossy_edges_reply(*args, **kwargs):
        # The edge-cached reply step's one exchange is its reply.
        sharded._all_to_all = lambda x: blank_peer(exchange(x))
        try:
            return edges_reply(*args, **kwargs)
        finally:
            sharded._all_to_all = exchange

    def lossy_reply_core(game, S, qcap, local, reply, *rest):
        # The lookup backward's reply arrives here after its exchange.
        if reply is not None:
            reply = blank_peer(reply)
        return reply_core(game, S, qcap, local, reply, *rest)

    sharded._sharded_edges_reply_step = lossy_edges_reply
    sharded._reply_core = lossy_reply_core


def main(argv):
    fault, rest = argv[0], argv[1:]
    if fault == "lost_peer":
        from gamesmanmpi_tpu.parallel import sharded

        lose_a_peer(sharded)
    elif fault != "none":
        raise SystemExit(f"mesh_fault_run.py: no fault {fault!r}")
    import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
