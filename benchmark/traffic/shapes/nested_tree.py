"""Shape ``nested_tree``: ``nested_tree``'s add-dominated outline of
nested branches, written in bursts (configuration keys ``ops``,
``replicas``, ``depth``, ``burst``)."""
from benchmark.traffic import workloads


def make_log(config: dict, rng) -> workloads.DocLog:
    return workloads.nested_log(config["ops"], config["replicas"],
                                config["depth"], config["burst"], rng)
