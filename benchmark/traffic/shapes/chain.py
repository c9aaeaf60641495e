"""Shape ``chain``: ``chain_workload``'s concurrent insertion chains,
one per replica, every chain starting at the branch head (configuration
keys ``replicas``, ``ops``)."""
from benchmark.traffic import workloads


def make_log(config: dict, rng) -> workloads.DocLog:
    return workloads.chain_log(config["replicas"], config["ops"], rng)
