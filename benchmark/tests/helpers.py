"""A cell's own files, with its graph cut to a size a CPU test holds."""

from benchmark import run

CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_spec(workload: str, peers: int, attestations: int) -> run.Spec:
    spec = run.load_spec(workload)
    spec.config["graph"].update(peers=peers, attestations=attestations)
    return spec
