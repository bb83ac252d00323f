"""Mean over the window's solves of the sharded engine's own
``spill_retries`` (its ``done`` record under ``--jsonl``): level steps run
again because a shard sent a peer more than the exchange's capacity. On a
mesh each is a whole extra exchange. An engine that routes nothing
reports no such field, and gives nothing."""


def read(ctx):
    got = [s["done"]["spill_retries"] for s in ctx["solves"]
           if "spill_retries" in s["done"]]
    return sum(got) / len(got) if got else None
