"""Elastic heterogeneous cluster demo: rating-based allocation (paper §V)
plus the beyond-paper elastic runtime — a worker dies mid-service, a second
straggles, and the cluster re-plans with the full Planner search (mode x
fusion x subset x transport, Eq. 7 overflow redistribution inside) while
keeping every surviving worker inside its memory budget.

Run:  PYTHONPATH=src python examples/heterogeneous_cluster.py
"""
import numpy as np

from repro.core import WorkerParams
from repro.models import mobilenet_v2_smoke
from repro.runtime.elastic import ElasticCluster


def show(cluster, tag):
    plan = cluster.plan
    macs = [plan.split.worker_macs(slot) / 1e3
            for slot in range(plan.n_workers)]
    print(f"{tag}: alive={cluster.alive_indices} "
          f"serving={list(cluster.plan_worker_ids)} "
          f"mode={plan.mode}/{plan.transport} "
          f"share(kMACs)={np.round(macs).astype(int).tolist()} "
          f"peakRAM(KB)={np.round(plan.peak_ram / 1024, 1).tolist()}")


def main():
    model = mobilenet_v2_smoke()
    workers = [WorkerParams(f_mhz=600, flash_bytes=64 << 10),
               WorkerParams(f_mhz=600, flash_bytes=24 << 10),   # small flash
               WorkerParams(f_mhz=450, flash_bytes=64 << 10),
               WorkerParams(f_mhz=150, flash_bytes=64 << 10)]
    cluster = ElasticCluster(model, workers, heartbeat_timeout=0.5)
    show(cluster, "initial plan   ")
    print("  (worker 1's small flash caps its share; the planner's Eq. 7 "
          "redistribution keeps every shard inside flash)")

    # steady state: heartbeats + step times flow in
    for w in cluster.alive_indices:
        cluster.heartbeat(w)
        cluster.report_step_time(w, 1.0)

    # worker 3 starts straggling (thermal throttle, contention, ...)
    for _ in range(3):
        cluster.report_step_time(3, 4.0)
    if cluster.check():
        show(cluster, "post-straggler ")
        print(f"  worker 3 demoted to {cluster.health[3].params.f_mhz:.0f} "
              f"MHz (floored at {cluster.demotion_floor:.0%} of original)")

    # worker 2 dies (no heartbeat); the rest keep heartbeating
    cluster.mark_failed(2)
    for w in cluster.alive_indices:
        cluster.heartbeat(w)
    cluster.check()
    show(cluster, "post-failure   ")

    print(f"re-planned inference latency: "
          f"{cluster.plan.latency_s * 1e3:.1f} ms "
          f"(simulated, transport={cluster.plan.transport})")

    # worker 2 comes back with a fresh process: original rating restored
    cluster.rejoin(2)
    for w in cluster.alive_indices:
        cluster.heartbeat(w)
    cluster.check()
    show(cluster, "post-rejoin    ")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
