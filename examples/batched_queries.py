"""Answer every tenant's full all-thresholds table from one compiled call.

    PYTHONPATH=src python examples/batched_queries.py

64 tenant streams share one hash group.  After ingest, a single snapshot
answers 64 streams x every threshold -- the fused batched query engine
(DESIGN.md §12) stacks all windows into one (N, levels, t, w) tensor and
runs ONE compiled dispatch (moments, depth medians, the Eq. 4 inversion,
suffix-sum g_k table, all streams at once).  The per-stream numpy oracle
(`reference_snapshot`) answers the identical query set
for comparison, and a standing-query poll loop shows the steady-state cost
with the version-keyed cache: unchanged windows are pure lookups, and one
flush invalidates exactly the streams whose windows changed.
"""
import time

import numpy as np

from repro.core import sjpc
from repro.service import EstimationService, ServiceConfig, reference_snapshot

D, S, TENANTS, RECORDS = 6, 4, 64, 2048

svc = EstimationService(ServiceConfig(batch_rows=512, window_epochs=4))
svc.create_group("tenants", sjpc.SJPCConfig(d=D, s=S, ratio=0.5,
                                            width=2048, depth=3))
rng = np.random.default_rng(0)
names = [f"tenant-{i:02d}" for i in range(TENANTS)]
for nm in names:
    svc.create_stream(nm, "tenants")
    svc.ingest(nm, rng.integers(0, 2000, size=(RECORDS, D), dtype=np.uint32))
svc.flush()

# -- one batched snapshot vs the per-stream reference oracle ---------------
svc.engine.snapshot().all_thresholds(names[0])   # compile the batched call
svc.engine._cache.clear()                        # time compute, not caching
for tag, snap in (("fused batched", svc.engine.snapshot()),
                  ("per-stream oracle", reference_snapshot(svc.registry))):
    t0 = time.perf_counter()
    tables = {nm: snap.all_thresholds(nm) for nm in names}
    dt = 1e3 * (time.perf_counter() - t0)
    cells = sum(len(t) for t in tables.values())
    print(f"{tag:>18}: {cells} (stream, threshold) cells in {dt:7.2f} ms")

fused = svc.engine.snapshot().all_thresholds(names[0])
oracle = reference_snapshot(svc.registry).all_thresholds(names[0])
print(f"\n{names[0]} all-thresholds (fused vs oracle):")
for k in fused:
    print(f"  g_{k} = {fused[k].estimate:>12.1f} +/- {fused[k].stderr:>10.1f}"
          f"   (oracle {oracle[k].estimate:>12.1f})")

# -- steady-state polling: the version-keyed cache ------------------------
snapshots = 200
t0 = time.perf_counter()
for _ in range(snapshots):
    snap = svc.engine.snapshot(names[:16])
    for nm in names[:16]:
        snap.all_thresholds(nm)
dt = time.perf_counter() - t0
print(f"\nsteady-state polling (16 streams x all thresholds, window "
      f"unchanged): {snapshots / dt:7.0f} snapshots/s "
      f"({1e3 * dt / snapshots:.2f} ms each)")

svc.ingest(names[0], rng.integers(0, 2000, size=(256, D), dtype=np.uint32))
svc.flush()                      # bumps tenant-00's window version
r = svc.engine.snapshot([names[0]]).self_join(names[0])
print(f"after one more flush, {names[0]} g_{S} = {r.estimate:.1f} "
      f"(cache refreshed by window version, never stale)")
