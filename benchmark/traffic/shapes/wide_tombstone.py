"""Shape ``wide_tombstone``: ``tombstone_heavy``'s wide fan-out (BASELINE
config 4).  Every replica adds its children directly at the root,
anchored at the branch head, then deletes ``delete_share`` of its own
children (configuration keys ``replicas``, ``adds``, ``delete_share``).

As ``tombstone_heavy`` orders them: all adds before all deletes, each
part replica by replica in ascending id order.  The seed picks the
replica ids and each replica's victims; every add's value is its index
in the log."""
from benchmark.traffic import wire, workloads


def make_log(config: dict, rng) -> workloads.DocLog:
    n_replicas = config["replicas"]
    per = config["adds"] // n_replicas
    rids = sorted(workloads.replica_ids(rng, n_replicas))
    adds = (rid * workloads.OFFSET + c
            for rid in rids for c in range(1, per + 1))
    ops = [("add", (0,), ts, i) for i, ts in enumerate(adds)]
    n_doomed = int(per * config["delete_share"])
    for rid in rids:
        ops.extend(("del", (rid * workloads.OFFSET + c,))
                   for c in rng.sample(range(1, per + 1), n_doomed))
    return workloads.DocLog(body=wire.dumps_ops(ops), n_ops=len(ops),
                            ops=ops)
