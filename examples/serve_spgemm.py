"""SparseService quickstart: overload-safe SpGEMM serving in five scenes.

The paper's Reuse case at serving rates: many requests, few structures,
every reply a pinned-plan replay. This script walks the serving tier's
whole contract on CPU:

  1. admission + grouped dispatch — mixed-structure traffic, one device
     dispatch per structure group, every reply bitwise-checked against the
     fresh spgemm() reference
  2. backpressure — a burst past the queue bound sheds with typed
     ``AdmissionRejected``, never an unbounded queue, never a silent drop
  3. deadlines — an infeasible deadline is refused at the door, an expired
     one is shed from the queue as ``DeadlineExceeded``; everything else
     completes
  4. breaker under kernel faults — the fast Pallas path starts failing
     (injected), the degradation ladder keeps every reply bitwise-correct,
     the circuit breaker opens and routes traffic straight to XLA, and a
     half-open probe re-admits the fast path once it heals
  5. warming — the service's own traffic log prefetches the hot plans after
     an eviction, so the next burst never pays a plan build
  6. observability — turn tracing on for a burst: request trace ids ride
     every span into a Chrome trace export, the service's ``serve.request``
     latency histogram gives p50/p99, and ``stats(debug=True)`` returns the
     flight-recorder ring (tracing off costs nothing — see
     ``benchmarks.run --bench obs``)

Run: PYTHONPATH=src python examples/serve_spgemm.py
"""
import jax.numpy as jnp

from repro import obs
from repro.core import spgemm, telemetry
from repro.runtime import AdmissionRejected, DeadlineExceeded, faults
from repro.serve import SparseService
from repro.sparse import random_csr


class Clock:
    """A hand-cranked clock so the deadline/breaker scenes are exact."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def main():
    structures = [
        (random_csr(48, 32, 3.0, 1), random_csr(32, 40, 3.0, 2)),
        (random_csr(24, 32, 2.0, 3), random_csr(32, 16, 2.0, 4)),
    ]
    refs = [spgemm(a, b, method="sparse").c.to_dense() for a, b in structures]
    clock = Clock()
    svc = SparseService(backend="pallas", max_queue=8, max_batch=4,
                        breaker_threshold=2, breaker_cooldown_s=5.0,
                        clock=clock, sleep=lambda _: None)

    # 1. mixed traffic: grouped into one dispatch per structure ------------
    reqs = [svc.submit(*structures[i % 2]) for i in range(6)]
    svc.drain()
    for i, r in enumerate(reqs):
        assert r.ok and bool(jnp.all(r.value.to_dense() == refs[i % 2]))
    print(f"1. served {len(reqs)} requests in "
          f"{svc.counters['group_dispatches']} group dispatches "
          f"(group sizes: {sorted(r.group_size for r in reqs)})")

    # 2. backpressure: the queue bound sheds, typed ------------------------
    burst = [svc.submit(*structures[0]) for _ in range(12)]
    rejected = [r for r in burst if isinstance(r.error, AdmissionRejected)]
    assert len(rejected) == 4  # 8 admitted (max_queue), 4 refused
    svc.drain()
    assert all(r.ok for r in burst if r not in rejected)
    print(f"2. burst of {len(burst)}: {len(rejected)} shed with "
          f"AdmissionRejected, the rest completed")

    # 3. deadlines: refused at the door, shed from the queue ---------------
    svc.metrics.reset()    # forget the measured (fast) steps for this demo
    svc.step_hint_s = 0.5  # pretend a step costs 0.5s (seeds the estimator)
    infeasible = svc.submit(*structures[0], deadline_s=0.1)
    assert isinstance(infeasible.error, AdmissionRejected)
    expired = svc.submit(*structures[0], deadline_s=1.0)
    fine = svc.submit(*structures[1], deadline_s=60.0)
    clock.now += 2.0  # the queue sat longer than the first deadline
    svc.drain()
    assert isinstance(expired.error, DeadlineExceeded) and fine.ok
    print("3. deadlines: 0.1s refused at admission (est wait 0.5s), 1.0s "
          "expired in queue -> DeadlineExceeded, 60s completed")

    # 4. kernel faults: ladder keeps replies correct, breaker stops paying -
    def serve_one():
        r = svc.submit(*structures[0])
        svc.step()
        assert r.ok and bool(jnp.all(r.value.to_dense() == refs[0]))
        return r

    with faults.failpoint("kernel:pallas"):
        degraded = [serve_one().degraded for _ in range(4)]
    opens = telemetry.BREAKER_COUNTS["pallas:open"]
    shorts = telemetry.BREAKER_COUNTS["pallas:short_circuit"]
    print(f"4. fault window: degraded={degraded} (breaker opened after "
          f"{svc._breakers['pallas'].failure_threshold}; opens={opens}, "
          f"short_circuits={shorts} requests skipped the broken kernel; "
          f"every reply still bitwise-correct)")
    clock.now += 5.0  # cooldown elapses, kernel healed
    r = serve_one()
    assert r.backend == "pallas" and not r.degraded
    print(f"4. recovery: half-open probe succeeded, breaker "
          f"{svc._breakers['pallas'].state}, traffic back on pallas")

    # 5. warming from the service's own traffic log ------------------------
    svc.plan_cache.clear()  # an eviction storm
    stats = svc.warm()
    misses0 = svc.plan_cache.stats()["misses"]
    svc.submit(*structures[0])
    svc.submit(*structures[1])
    svc.drain()
    assert svc.plan_cache.stats()["misses"] == misses0
    print(f"5. warmed {stats['built']} plans from the traffic log; the next "
          f"burst ran with zero plan-cache misses")

    # 6. observability: trace a burst, read request latency, dump the ring -
    obs.set_tracing("on")  # or REPRO_TRACE=1, or spgemm(..., trace=True)
    traced = [svc.submit(*structures[i % 2]) for i in range(4)]
    svc.drain()
    assert all(r.ok for r in traced)
    payload = obs.export_chrome_trace("trace_serve_quickstart.json")
    spans = payload["traceEvents"]
    tids = sorted({e["args"].get("trace_id") for e in spans
                   if e["args"].get("trace_id")})
    debug = svc.stats(debug=True)
    req = debug["request_latency"]
    print(f"6. traced burst: {len(spans)} spans from requests {tids} -> "
          f"trace_serve_quickstart.json (open in chrome://tracing); "
          f"serve.request p50={req['p50']*1e6:.0f}us "
          f"p99={req['p99']*1e6:.0f}us over {req['count']} requests; "
          f"flight recorder holds {debug['flight_recorder']['recorded']} "
          f"events")
    obs.set_tracing(None)  # back to the $REPRO_TRACE default (off)

    print(f"\nfinal stats: completed={svc.counters['completed']} "
          f"shed_rate={svc.stats()['shed_rate']:.3f} "
          f"breaker={svc.stats()['breakers']['pallas']['state']}")
    print("OK")


if __name__ == "__main__":
    main()
