"""Serving resilience bench: load ramp at 1x/2x/4x capacity + recovery.

Drives an :class:`~accelerate_tpu.serving.InferenceServer` with a synthetic
constant-service-time engine (capacity = max_batch / service_s, so the
overload multiples are exact) through five phases:

- ``baseline``  — offered load at 1x capacity
- ``over_2x``   — 2x capacity: queue fills, deadline shedding engages
- ``over_4x``   — 4x capacity: bounded queue + typed rejections under stress
- ``fault``     — every batch fails: retries exhaust, the breaker opens
- ``recovery``  — faults cleared: breaker closes, throughput must return to
  >= ``SB_GATE_RECOVERY`` (default 95%) of baseline

plus a SIGTERM probe (``--sigterm-child`` sub-mode): the bench re-spawns
itself under load, sends SIGTERM mid-batch, and asserts exit code 143 with
every in-flight future resolved (result or typed rejection — none dropped).

Prints one JSON line per phase plus a gate line. ``--gate`` (also reached
via ``bench.py --serving-gate`` / ``make bench-serving``) turns the
acceptance criteria into a nonzero exit: bounded queue, only typed shed
errors, accepted p99 within deadline, recovery throughput, SIGTERM drain.
"""

from __future__ import annotations

import os
import sys as _sys

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import json
import signal
import subprocess
import time

import numpy as np

SERVICE_S = float(os.environ.get("SB_SERVICE_S", "0.04"))
MAX_BATCH = int(os.environ.get("SB_MAX_BATCH", "8"))
PHASE_S = float(os.environ.get("SB_PHASE_S", "1.5"))
DEADLINE_S = float(os.environ.get("SB_DEADLINE_S", "0.25"))
GATE_RECOVERY = float(os.environ.get("SB_GATE_RECOVERY", "0.95"))
PROMPT = np.arange(1, 9, dtype=np.int32)


class _SyntheticEngine:
    """generate_fn with a fixed per-batch service time — capacity is exactly
    ``max_batch / service_s`` rps, so the ramp multiples mean what they say.
    ``fail=True`` turns every batch into an immediate device fault."""

    def __init__(self, service_s: float):
        self.service_s = service_s
        self.fail = False
        self.batches = 0

    def __call__(self, model, ids, max_new_tokens=4, **kw):
        if self.fail:
            raise RuntimeError("injected device fault")
        time.sleep(self.service_s)
        self.batches += 1
        new = np.repeat(ids[:, :1], max_new_tokens, axis=1)
        return np.concatenate([ids, new], axis=1)


def _p(latencies, q):
    if not latencies:
        return None
    s = sorted(latencies)
    return s[min(len(s) - 1, int(q * len(s)))]


def _run_phase(srv, name, rate_rps, duration_s):
    from accelerate_tpu.utils.fault import (
        RequestDeadlineExceeded,
        ServingError,
    )

    futures = []
    admission = {"queue_full": 0, "breaker": 0, "draining": 0}
    untyped = 0
    max_depth = 0
    start = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter()
        if now - start >= duration_s:
            break
        next_t = start + i / rate_rps
        if next_t > now:
            time.sleep(min(next_t - now, 0.01))
            continue
        i += 1
        try:
            futures.append(
                srv.submit(PROMPT, max_new_tokens=4, deadline_s=DEADLINE_S)
            )
        except ServingError as exc:
            kind = type(exc).__name__
            key = {
                "ServerOverloaded": "queue_full",
                "CircuitOpenError": "breaker",
                "ServerDrainingError": "draining",
            }.get(kind)
            if key is None or not hasattr(exc, "retriable"):
                untyped += 1
            else:
                admission[key] += 1
        except Exception:  # noqa: BLE001 — gate counts anything untyped
            untyped += 1
        max_depth = max(max_depth, srv.queue_depth())

    latencies, completed, shed, failed = [], 0, 0, 0
    for f in futures:
        try:
            res = f.result(timeout=30)
            completed += 1
            latencies.append(res.latency_s)
        except RequestDeadlineExceeded:
            shed += 1
        except ServingError:
            failed += 1
        except Exception:  # noqa: BLE001
            untyped += 1
    elapsed = time.perf_counter() - start
    offered = i + sum(admission.values())
    row = {
        "phase": name,
        "offered_rps": round(offered / elapsed, 1),
        "completed_rps": round(completed / elapsed, 1),
        "shed_rate": round(
            (shed + failed + sum(admission.values())) / max(offered, 1), 3
        ),
        "p50_s": round(_p(latencies, 0.50), 4) if latencies else None,
        "p99_s": round(_p(latencies, 0.99), 4) if latencies else None,
        "deadline_s": DEADLINE_S,
        "rejected": admission,
        "batch_failed": failed,
        "max_queue_depth": max_depth,
        "untyped_errors": untyped,
    }
    print(json.dumps(row), flush=True)
    return row


def _sigterm_child() -> int:
    import atexit

    from accelerate_tpu.serving import InferenceServer, install_drain_handler
    from accelerate_tpu.utils.dataclasses import ServingConfig

    eng = _SyntheticEngine(0.05)
    cfg = ServingConfig(max_batch_size=2, batch_window_s=0.0, max_queue=64)
    srv = InferenceServer(object(), cfg, generate_fn=eng)
    install_drain_handler(srv)
    futs = [srv.submit(PROMPT, max_new_tokens=4) for _ in range(6)]

    def _report():
        done = sum(1 for f in futs if f.done())
        ok = sum(1 for f in futs if f.done() and f.exception() is None)
        print(
            json.dumps(
                {"result": "sigterm_child", "submitted": len(futs),
                 "done": done, "ok": ok}
            ),
            flush=True,
        )

    atexit.register(_report)
    print("READY", flush=True)
    while True:  # the drain handler sys.exit(143)s out of this
        time.sleep(0.1)


def _sigterm_probe() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [_sys.executable, os.path.abspath(__file__), "--sigterm-child"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
    )
    try:
        deadline = time.monotonic() + 60
        ready = False
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.strip() == "READY":
                ready = True
                break
        if not ready:
            proc.kill()
            return {"phase": "sigterm", "pass": False, "error": "child never READY"}
        time.sleep(0.05)  # land the signal mid-batch
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        return {"phase": "sigterm", "pass": False, "error": "child hung in drain"}
    report = None
    for line in out.splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if parsed.get("result") == "sigterm_child":
            report = parsed
    row = {
        "phase": "sigterm",
        "returncode": proc.returncode,
        "report": report,
        "pass": (
            proc.returncode == 143
            and report is not None
            and report["done"] == report["submitted"]  # zero dropped in-flight
            and report["ok"] >= 1
        ),
    }
    if not row["pass"]:
        row["stderr_tail"] = err[-500:]
    print(json.dumps(row), flush=True)
    return row


def main(gate: bool = False) -> int:
    from accelerate_tpu.serving import InferenceServer
    from accelerate_tpu.utils.dataclasses import ServingConfig

    eng = _SyntheticEngine(SERVICE_S)
    cfg = ServingConfig(
        max_queue=256,
        max_batch_size=MAX_BATCH,
        batch_window_s=0.001,
        default_max_new_tokens=4,
        max_retries=2,
        retry_backoff_s=0.02,
        retry_backoff_max_s=0.1,
        breaker_threshold=5,
        breaker_reset_s=0.3,
        drain_timeout_s=10.0,
    )
    capacity = MAX_BATCH / SERVICE_S
    rows = {}
    with InferenceServer(object(), cfg, generate_fn=eng) as srv:
        rows["baseline"] = _run_phase(srv, "baseline", capacity, PHASE_S)
        rows["over_2x"] = _run_phase(srv, "over_2x", 2 * capacity, PHASE_S)
        rows["over_4x"] = _run_phase(srv, "over_4x", 4 * capacity, PHASE_S)
        eng.fail = True
        rows["fault"] = _run_phase(srv, "fault", 0.5 * capacity, 0.4)
        eng.fail = False
        time.sleep(cfg.breaker_reset_s + 0.2)  # let the breaker reach HALF_OPEN
        rows["recovery"] = _run_phase(srv, "recovery", capacity, PHASE_S)
        breaker_open_at_end = srv._breaker.rejects_admission  # noqa: SLF001
        breaker_opened = srv.metrics["breaker_opens"] >= 1
    rows["sigterm"] = _sigterm_probe()

    recovery_ratio = rows["recovery"]["completed_rps"] / max(
        rows["baseline"]["completed_rps"], 1e-9
    )
    checks = {
        "typed_errors_only": all(r.get("untyped_errors", 0) == 0 for r in rows.values()),
        "queue_bounded": all(
            r.get("max_queue_depth", 0) <= cfg.max_queue for r in rows.values()
        ),
        "alive_at_4x": rows["over_4x"]["completed_rps"] > 0,
        "accepted_p99_within_deadline": all(
            rows[p]["p99_s"] is None or rows[p]["p99_s"] <= DEADLINE_S
            for p in ("baseline", "over_2x", "over_4x", "recovery")
        ),
        "breaker_opened_under_faults": breaker_opened,
        "breaker_closed_after_recovery": not breaker_open_at_end,
        "recovery_throughput": recovery_ratio >= GATE_RECOVERY,
        "sigterm_drain": rows["sigterm"]["pass"],
    }
    ok = all(checks.values())
    print(
        json.dumps(
            {
                "metric": "serving_resilience_gate",
                "capacity_rps": round(capacity, 1),
                "recovery_vs_baseline": round(recovery_ratio, 3),
                "threshold": GATE_RECOVERY,
                "checks": checks,
                "pass": ok,
            }
        ),
        flush=True,
    )
    return 0 if (ok or not gate) else 1


# ===================================================================== fleet
# Multi-replica fleet bench (PR 10): goodput ramp at 1x/2x/4x replicas, a
# chaos probe (kill one replica mid-batch under load: zero dropped futures),
# and TTFT p99 with vs without prefill/decode disaggregation. Reached via
# ``--fleet`` / ``--fleet-gate`` (also ``bench.py --fleet-gate`` /
# ``make bench-fleet``).

FLEET_PHASE_S = float(os.environ.get("SB_FLEET_PHASE_S", "1.5"))
FLEET_OFFERED_X = float(os.environ.get("SB_FLEET_OFFERED_X", "2.5"))
FLEET_GATE_SCALE = float(os.environ.get("SB_FLEET_GATE_SCALE", "1.8"))
FLEET_TTFT_TOL = float(os.environ.get("SB_FLEET_TTFT_TOL", "1.10"))
FLEET_SEED = int(os.environ.get("SB_FLEET_SEED", "0"))
# mixed long/short prompt profile for the fleet replay — the same seeded
# loadgen.PromptMix the long-context bench draws from, so both benches
# offer bit-identical length sequences run over run. The two-point length
# ranges keep the static batcher's group keys bounded (group key includes
# exact prompt length): the mix stresses mixed-length scheduling without
# dissolving every batch into singletons.
FLEET_MIX_LONG_FRAC = float(os.environ.get("SB_FLEET_MIX_LONG_FRAC", "0.2"))
FLEET_MIX_SHORT_LEN = int(os.environ.get("SB_FLEET_MIX_SHORT_LEN", "8"))
FLEET_MIX_LONG_LEN = int(os.environ.get("SB_FLEET_MIX_LONG_LEN", "32"))
# --cross-replica phase: remote prefill over TCP loopback vs in-process
# hand-off; the committed gate is TTFT p99 tcp <= 1.3x inproc
CROSS_TTFT_RATIO = float(os.environ.get("SB_CROSS_TTFT_RATIO", "1.3"))
CROSS_N = int(os.environ.get("SB_CROSS_N", "64"))
CROSS_GAP_S = float(os.environ.get("SB_CROSS_GAP_S", "0.01"))
CROSS_PROMPTS = int(os.environ.get("SB_CROSS_PROMPTS", "4"))


class _KillableEngine(_SyntheticEngine):
    """Synthetic engine whose next batch takes the whole serving worker
    down with SystemExit — the in-process analogue of SIGKILLing a replica
    mid-batch (a thread cannot be SIGKILLed individually)."""

    def __init__(self, service_s: float):
        super().__init__(service_s)
        self.kill_next = False

    def __call__(self, model, ids, max_new_tokens=4, **kw):
        if self.kill_next:
            self.kill_next = False
            raise SystemExit(1)
        return super().__call__(model, ids, max_new_tokens=max_new_tokens, **kw)


class _SynOccupant:
    """Slot-occupant stand-in: tag/budget/token bookkeeping plus the two
    attributes the reply epilogue reads (first_token_s, inserted_s)."""

    def __init__(self, prompt, budget, tag, now):
        self.prompt = np.asarray(prompt, dtype=np.int32)
        self.budget = budget
        self.tag = tag
        self.tokens = 0
        self.inserted_s = now
        self.first_token_s = None

    def output_row(self):
        new = np.repeat(self.prompt[:1], self.tokens)
        return np.concatenate([self.prompt, new])


class _SynPrefill:
    def __init__(self, engine, prompt, max_new_tokens):
        self.engine = engine
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens


class _SyntheticSlotEngine:
    """Continuous-engine stand-in with explicit prefill/decode costs, so
    the disaggregation comparison measures *scheduling*, not model math:

    * ``insert`` (the in-loop path) sleeps ``prefill_s`` — the decode loop
      stalls behind every prompt forward it runs itself;
    * ``prefill_remote`` sleeps ``prefill_s`` on the *calling* (prefill
      worker) thread; ``insert_prefilled`` commits in ~zero time — the
      decode loop only scatters precomputed KV;
    * ``step`` sleeps ``decode_step_s`` and advances every live slot one
      token.

    Implements exactly the engine surface InferenceServer's continuous
    loop drives (insert/step/poll/reset/occupants/cancel/stats/...).
    Thread-safe where the fleet needs it: prefill workers call
    ``prefill_remote`` while the serving worker steps."""

    spec = None  # no speculative decoding: the degrade ladder skips us

    def __init__(self, slots=8, prefill_s=0.02, decode_step_s=0.002):
        import threading

        self.slots = slots
        self.prefill_s = prefill_s
        self.decode_step_s = decode_step_s
        self._lock = threading.Lock()
        self._live = []
        self._retired = []

    # --- admission
    def validate_request(self, prompt_len, max_new_tokens):
        if prompt_len <= 0 or max_new_tokens <= 0:
            raise ValueError("empty prompt or budget")

    def can_admit(self, ids, max_new_tokens):
        return True

    def free_slots(self):
        with self._lock:
            return self.slots - len(self._live)

    def live_count(self):
        with self._lock:
            return len(self._live)

    def insert(self, prompt, max_new_tokens, tag=None, **kw):
        time.sleep(self.prefill_s)  # prompt forward runs IN the decode loop
        now = time.monotonic()
        occ = _SynOccupant(prompt, max_new_tokens, tag, now)
        occ.first_token_s = now  # prefill emits the first token
        with self._lock:
            self._live.append(occ)
        return occ

    # --- disaggregated path
    def prefill_remote(self, prompt, *, max_new_tokens, **kw):
        time.sleep(self.prefill_s)  # prompt forward on the PREFILL worker
        return _SynPrefill(self, np.asarray(prompt, np.int32), max_new_tokens)

    def accepts_prefill(self, pre):
        return isinstance(pre, _SynPrefill) and pre.engine is self

    def insert_prefilled(self, pre, *, max_new_tokens, tag=None):
        now = time.monotonic()
        occ = _SynOccupant(pre.prompt, max_new_tokens, tag, now)
        occ.first_token_s = now  # commit publishes the precomputed token
        with self._lock:
            self._live.append(occ)
        return occ

    # --- decode loop
    def step(self):
        time.sleep(self.decode_step_s)
        with self._lock:
            still = []
            for occ in self._live:
                occ.tokens += 1
                (self._retired if occ.tokens >= occ.budget else still).append(occ)
            self._live = still

    def poll(self, force=False):
        with self._lock:
            out, self._retired = self._retired, []
        return out

    def occupants(self):
        with self._lock:
            return list(self._live)

    def cancel(self, occ):
        with self._lock:
            if occ in self._live:
                self._live.remove(occ)

    def reset(self):
        with self._lock:
            orphans, self._live, self._retired = self._live, [], []
        return orphans

    def stats(self):
        with self._lock:
            return {"slots": self.slots, "live": len(self._live)}


def _fleet_imports():
    from accelerate_tpu.fleet import FleetRouter
    from accelerate_tpu.serving import InferenceServer
    from accelerate_tpu.utils.dataclasses import FleetConfig, ServingConfig

    return FleetRouter, InferenceServer, FleetConfig, ServingConfig


def _run_fleet_phase(router, name, rate_rps, duration_s, deadline_s=None,
                     mid_phase=None, schedule=None):
    """Seeded open-loop arrivals against the router (benchmarks/loadgen —
    same seed ⇒ same offered sequence every run). The router's contract is
    "always a Future", so admission failures surface on the futures —
    the gate wants exactly: every future resolves, failures are typed and
    retriable, nothing is dropped."""
    from benchmarks import loadgen

    from accelerate_tpu.utils.fault import (
        RequestDeadlineExceeded,
        ServingError,
    )

    if schedule is None:
        schedule = loadgen.constant(rate_rps, duration_s, seed=FLEET_SEED,
                                    name=name)
    mix = loadgen.PromptMix(
        short_lens=(FLEET_MIX_SHORT_LEN, FLEET_MIX_SHORT_LEN),
        long_lens=(FLEET_MIX_LONG_LEN, FLEET_MIX_LONG_LEN),
        long_fraction=FLEET_MIX_LONG_FRAC, seed=FLEET_SEED,
    )
    futures = []
    mix_counts = {"short": 0, "long": 0}
    start = time.perf_counter()
    fired_mid = mid_phase is None
    i = 0
    for t, _phase in schedule.arrivals:
        now = time.perf_counter()
        if not fired_mid and now - start >= schedule.duration_s / 2:
            fired_mid = True
            mid_phase()
        while True:
            lag = start + t - time.perf_counter()
            if lag <= 0:
                break
            time.sleep(min(lag, 0.01))
        i += 1
        prompt, kind = mix.next_prompt()
        mix_counts[kind] += 1
        futures.append(
            router.submit(np.asarray(prompt, np.int32), max_new_tokens=4,
                          deadline_s=deadline_s)
        )
    if not fired_mid:  # schedule ended before midpoint (shouldn't happen)
        mid_phase()

    ttfts, latencies = [], []
    completed = shed = typed_retriable = typed_final = untyped = dropped = 0
    for f in futures:
        try:
            res = f.result(timeout=30)
            completed += 1
            latencies.append(res.latency_s)
            if res.ttft_s is not None:
                ttfts.append(res.ttft_s)
        except RequestDeadlineExceeded:
            shed += 1
        except ServingError as exc:
            if exc.retriable:
                typed_retriable += 1
            else:
                typed_final += 1
        except TimeoutError:
            dropped += 1  # the zero-drop gate: this must stay 0
        except Exception:  # noqa: BLE001 — gate counts anything untyped
            untyped += 1
    elapsed = time.perf_counter() - start
    row = {
        "phase": name,
        "prompt_mix": mix_counts,
        "offered_rps": round(i / elapsed, 1),
        "goodput_rps": round(completed / elapsed, 1),
        "shed": shed,
        "typed_retriable": typed_retriable,
        "typed_final": typed_final,
        "untyped_errors": untyped,
        "dropped_futures": dropped,
        "p99_s": round(_p(latencies, 0.99), 4) if latencies else None,
        "ttft_p99_s": round(_p(ttfts, 0.99), 4) if ttfts else None,
    }
    print(json.dumps(row), flush=True)
    return row


def _fleet_ramp(n_replicas):
    """Goodput at fixed offered load (FLEET_OFFERED_X × one replica's
    capacity) as the fleet scales — the scaling gate compares 2x vs 1x."""
    FleetRouter, InferenceServer, FleetConfig, ServingConfig = _fleet_imports()
    capacity = MAX_BATCH / SERVICE_S
    scfg = ServingConfig(
        max_queue=256, max_batch_size=MAX_BATCH, batch_window_s=0.001,
        default_max_new_tokens=4, max_retries=0, drain_timeout_s=10.0,
    )
    servers = {
        f"r{i}": InferenceServer(
            object(), scfg, generate_fn=_SyntheticEngine(SERVICE_S),
            replica_id=f"r{i}",
        )
        for i in range(n_replicas)
    }
    router = FleetRouter(servers, FleetConfig(probe_interval_s=0.1))
    try:
        return _run_fleet_phase(
            router, f"ramp_{n_replicas}x", FLEET_OFFERED_X * capacity,
            FLEET_PHASE_S, deadline_s=DEADLINE_S,
        )
    finally:
        router.close(drain=False)


def _fleet_chaos():
    """Kill one of three replicas mid-batch at mid-phase under load. The
    acceptance bar: every submitted future resolves — completed or typed-
    retriable (and transparently failed over) — with zero drops."""
    FleetRouter, InferenceServer, FleetConfig, ServingConfig = _fleet_imports()
    capacity = MAX_BATCH / SERVICE_S
    scfg = ServingConfig(
        max_queue=256, max_batch_size=MAX_BATCH, batch_window_s=0.001,
        default_max_new_tokens=4, max_retries=0, drain_timeout_s=10.0,
    )
    engines = [_KillableEngine(SERVICE_S) for _ in range(3)]
    servers = {
        f"r{i}": InferenceServer(
            object(), scfg, generate_fn=engines[i], replica_id=f"r{i}"
        )
        for i in range(3)
    }
    router = FleetRouter(servers, FleetConfig(probe_interval_s=0.05))

    def kill_one():
        engines[0].kill_next = True

    try:
        row = _run_fleet_phase(
            router, "chaos_kill", 1.5 * capacity, FLEET_PHASE_S,
            mid_phase=kill_one,
        )
        row["failovers"] = router.metrics["failovers"]
        row["probe_failures"] = router.metrics["probe_failures"]
        print(json.dumps({"phase": "chaos_kill_router",
                          "failovers": row["failovers"],
                          "probe_failures": row["probe_failures"]}), flush=True)
        return row
    finally:
        router.close(drain=False)


def _fleet_ttft(disaggregate):
    """TTFT p99 through a continuous-mode replica under a prompt burst,
    with and without dedicated prefill workers. Costs are explicit in
    _SyntheticSlotEngine, so the delta is pure scheduling: in-loop prompt
    forwards serialize behind each other; remote prefills overlap."""
    FleetRouter, InferenceServer, FleetConfig, ServingConfig = _fleet_imports()
    eng = _SyntheticSlotEngine(slots=8, prefill_s=0.02, decode_step_s=0.002)
    scfg = ServingConfig(
        mode="continuous", max_queue=256, default_max_new_tokens=4,
        drain_timeout_s=10.0,
    )
    srv = InferenceServer(object(), scfg, engine=eng, replica_id="decode-0")
    router = FleetRouter(
        {"decode-0": srv},
        FleetConfig(
            probe_interval_s=0.1,
            disaggregate_prefill=disaggregate,
            prefill_workers=4,
        ),
    )
    name = "ttft_disagg" if disaggregate else "ttft_plain"
    try:
        futs = [router.submit(PROMPT, max_new_tokens=4) for _ in range(48)]
        ttfts = [f.result(timeout=30).ttft_s for f in futs]
        row = {
            "phase": name,
            "n": len(ttfts),
            "ttft_p50_s": round(_p(ttfts, 0.50), 4),
            "ttft_p99_s": round(_p(ttfts, 0.99), 4),
            "remote_prefills": router.metrics["prefills"],
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        router.close(drain=False)


def _cross_replica_phase(transport):
    """One cross-replica disaggregation run over the given KV transport
    (``accelerate_tpu.kvtransfer``): two continuous replicas, every
    remote prefill shipped through the transactional chunk protocol, a
    repeated prompt set so gossiped prefix digests give KV-affinity
    routing something to hit. The synthetic engine (benchmarks/kv_synth)
    carries real bytes with real epoch fencing but explicit costs, so
    the inproc-vs-tcp TTFT delta is pure transport."""
    from benchmarks.kv_synth import SynthKVEngine

    FleetRouter, InferenceServer, FleetConfig, ServingConfig = _fleet_imports()
    scfg = ServingConfig(
        mode="continuous", max_queue=256, default_max_new_tokens=4,
        drain_timeout_s=10.0,
    )
    servers = {
        f"r{i}": InferenceServer(
            object(), scfg,
            engine=SynthKVEngine(slots=8, prefill_s=0.02,
                                 decode_step_s=0.002),
            replica_id=f"r{i}",
        )
        for i in range(2)
    }
    router = FleetRouter(servers, FleetConfig(
        probe_interval_s=0.05,
        disaggregate_prefill=True,
        prefill_workers=4,
        kv_transfer=transport,
        kv_transfer_chunk_bytes=2048,
    ))
    prompts = [
        np.arange(p * 100 + 1, p * 100 + 17, dtype=np.int32)
        for p in range(CROSS_PROMPTS)
    ]
    rng = np.random.default_rng(FLEET_SEED)
    try:
        # warm wave: seed every prompt's prefix blocks somewhere in the
        # fleet, then let two probe passes gossip the digests
        warm = [router.submit(p, max_new_tokens=4) for p in prompts]
        for f in warm:
            f.result(timeout=30)
        time.sleep(0.15)
        hits0 = router.metrics["kv_affinity_hits"]
        transfers0 = router.metrics["kv_transfers"]
        futs = []
        for _ in range(CROSS_N):
            futs.append(router.submit(
                prompts[int(rng.integers(len(prompts)))], max_new_tokens=4,
            ))
            time.sleep(CROSS_GAP_S)  # paced: TTFT measures service, not queue
        ttfts = [f.result(timeout=30).ttft_s for f in futs]
        m = router.metrics
        hits = m["kv_affinity_hits"] - hits0
        row = {
            "phase": f"cross_replica_{transport}",
            "n": len(ttfts),
            "ttft_p50_s": round(_p(ttfts, 0.50), 4),
            "ttft_p99_s": round(_p(ttfts, 0.99), 4),
            "kv_transfers": m["kv_transfers"] - transfers0,
            "affinity_hits": hits,
            "prefix_hit_rate": round(hits / max(len(ttfts), 1), 3),
            "fallbacks": (
                m["prefill_fallback/unavailable"]
                + m["prefill_fallback/transfer_failed"]
                + m["prefill_fallback/stale_epoch"]
            ),
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        router.close(drain=False)


def cross_replica_main(gate: bool = False) -> int:
    inproc = _cross_replica_phase("inproc")
    tcp = _cross_replica_phase("tcp")
    ratio = tcp["ttft_p99_s"] / max(inproc["ttft_p99_s"], 1e-9)
    checks = {
        "wire_flowed": inproc["kv_transfers"] >= 1 and tcp["kv_transfers"] >= 1,
        "zero_fallbacks": inproc["fallbacks"] == 0 and tcp["fallbacks"] == 0,
        "affinity_observed": tcp["affinity_hits"] >= 1,
        "ttft_tcp_bounded": ratio <= CROSS_TTFT_RATIO,
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "cross_replica_gate",
        "ttft_p99_inproc": inproc["ttft_p99_s"],
        "ttft_p99_tcp": tcp["ttft_p99_s"],
        "ttft_ratio": round(ratio, 3),
        "ttft_threshold": CROSS_TTFT_RATIO,
        "prefix_hit_rate_tcp": tcp["prefix_hit_rate"],
        "checks": checks,
        "pass": ok,
    }), flush=True)
    return 0 if (ok or not gate) else 1


def fleet_main(gate: bool = False) -> int:
    ramp = {n: _fleet_ramp(n) for n in (1, 2, 4)}
    chaos = _fleet_chaos()
    ttft_plain = _fleet_ttft(False)
    ttft_disagg = _fleet_ttft(True)

    scale_2x = ramp[2]["goodput_rps"] / max(ramp[1]["goodput_rps"], 1e-9)
    scale_4x = ramp[4]["goodput_rps"] / max(ramp[1]["goodput_rps"], 1e-9)
    checks = {
        "goodput_scales_2x": scale_2x >= FLEET_GATE_SCALE,
        "chaos_zero_dropped": chaos["dropped_futures"] == 0,
        "chaos_typed_only": chaos["untyped_errors"] == 0
        and chaos["typed_final"] == 0,
        "chaos_failed_over": chaos["failovers"] >= 1,
        "ttft_disagg_no_worse": (
            ttft_disagg["ttft_p99_s"] <= ttft_plain["ttft_p99_s"] * FLEET_TTFT_TOL
        ),
        "ttft_used_remote_prefill": ttft_disagg["remote_prefills"] >= 1,
        "ramp_zero_dropped": all(
            r["dropped_futures"] == 0 and r["untyped_errors"] == 0
            for r in ramp.values()
        ),
    }
    ok = all(checks.values())
    print(
        json.dumps(
            {
                "metric": "fleet_gate",
                "goodput_1x": ramp[1]["goodput_rps"],
                "goodput_2x": ramp[2]["goodput_rps"],
                "goodput_4x": ramp[4]["goodput_rps"],
                "scale_2x": round(scale_2x, 2),
                "scale_4x": round(scale_4x, 2),
                "scale_threshold": FLEET_GATE_SCALE,
                "ttft_p99_plain": ttft_plain["ttft_p99_s"],
                "ttft_p99_disagg": ttft_disagg["ttft_p99_s"],
                "checks": checks,
                "pass": ok,
            }
        ),
        flush=True,
    )
    return 0 if (ok or not gate) else 1


if __name__ == "__main__":
    if "--sigterm-child" in _sys.argv:
        raise SystemExit(_sigterm_child())
    if "--fleet" in _sys.argv or "--fleet-gate" in _sys.argv:
        _gate = "--fleet-gate" in _sys.argv
        _rc = fleet_main(gate=_gate)
        if "--cross-replica" in _sys.argv:
            _rc = max(_rc, cross_replica_main(gate=_gate))
        raise SystemExit(_rc)
    if "--cross-replica" in _sys.argv:
        raise SystemExit(cross_replica_main(gate="--gate" in _sys.argv))
    raise SystemExit(main(gate="--gate" in _sys.argv))
