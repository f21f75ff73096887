"""brpc_tpu.serving — inference serving on the RPC/ICI stack.

Three cooperating pieces (see README "Serving"):

  * :class:`DynamicBatcher` (batcher.py) — deadline-aware dynamic
    batching of concurrent unary RPCs into bucket-padded tensor calls;
  * :class:`DecodeEngine` (engine.py) — continuous-batching
    autoregressive decode over a fixed slot pool with KV state leased
    from the ICI BlockPool (raw blocks, or paged sequences through a
    :class:`brpc_tpu.kvcache.KVCacheStore` for radix prefix reuse);
  * :func:`register_serving` (service.py) — server glue exposing
    ``Serving.Score`` (batched unary) and ``Serving.Generate``
    (streaming decode) plus the chunked-HTTP generate route;
  * :class:`EngineSupervisor` (supervisor.py) — step-loop watchdog,
    crash recovery (in-flight decode failover over the surviving KV
    cache) and the overload degradation ladder; its ``submit`` has the
    engine's signature so it drops into ``register_serving``
    unchanged.

Every live batcher/engine/supervisor self-registers here (weakly, by
name) so the ``/serving`` builtin-console page can render batch
occupancy, the slot map, shed/pad statistics, and supervisor state
without holding components alive.

GENERATION TIMELINE (ISSUE 5): every retired decode attempt (engine)
and every completed supervised generation (supervisor) appends a
summary record to a bounded ring here — request/trace ids, TTFT,
inter-token latency, prefill-skip, restart count — which the
``/serving/generations`` console page renders alongside the aggregate
``serving_ttft_us`` / ``serving_itl_us`` recorders.
"""
from __future__ import annotations

import threading
from brpc_tpu.butil.lockprof import InstrumentedLock
import weakref
from collections import deque

_reg_mu = InstrumentedLock("serving.registry")
_batchers: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()
_engines: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()
_supervisors: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()
_routers: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()


def _register_batcher(b) -> None:
    with _reg_mu:
        _batchers[b.name] = b


def _register_engine(e) -> None:
    with _reg_mu:
        _engines[e.name] = e


def _register_supervisor(s) -> None:
    with _reg_mu:
        _supervisors[s.name] = s


def _register_router(r) -> None:
    with _reg_mu:
        _routers[r.name] = r


def cluster_snapshot() -> dict:
    """Live routers' stats — the /cluster console page's data: per
    router the replica table (health / breaker / quarantine / ladder
    level), session counts, resume stats, and the gradient's per-level
    fire counters."""
    with _reg_mu:
        routers = dict(_routers)
    return {
        "routers": {name: r.stats() for name, r in sorted(routers.items())},
    }


def fleet_snapshot(points: int = 32) -> dict:
    """Live routers' fleet telemetry (ISSUE 20) — the /fleet console
    page's data: per router the collector state (pulls, bytes,
    tombstones), the windowed series rings, per-model scoreboard,
    canary ramp state and the SLO decision trail."""
    with _reg_mu:
        routers = dict(_routers)
    return {
        "routers": {name: r.fleet_snapshot(points)
                    for name, r in sorted(routers.items())},
    }


def fleet_trace_spans(trace_id: int) -> list:
    """Cross-process spans of one trace, fanned out through every live
    router (the /rpcz?trace_id= stitching read) — empty when no router
    is registered or nothing was collected."""
    with _reg_mu:
        routers = dict(_routers)
    merged: dict[tuple, object] = {}
    for r in routers.values():
        try:
            for s in r.trace_fanout(trace_id):
                merged.setdefault(
                    (s.trace_id, s.span_id, s.kind, s.start_us), s)
        except Exception:
            continue
    return list(merged.values())


def serving_snapshot() -> dict:
    """Live components' stats — the /serving console page's data."""
    with _reg_mu:
        batchers = dict(_batchers)
        engines = dict(_engines)
        supervisors = dict(_supervisors)
    return {
        "batchers": {name: b.stats() for name, b in sorted(batchers.items())},
        "engines": {name: e.stats() for name, e in sorted(engines.items())},
        "supervisors": {name: s.stats()
                        for name, s in sorted(supervisors.items())},
    }


# ---- recent-generation ring (the /serving/generations console page) ----

_GEN_KEEP = 256
_gen_mu = InstrumentedLock("serving.generations")
_recent_gens: deque = deque(maxlen=_GEN_KEEP)


def record_generation(rec: dict) -> None:
    """Append one finished generation/attempt summary (bounded ring)."""
    with _gen_mu:
        _recent_gens.append(rec)


def recent_generations(limit: int = 50) -> list[dict]:
    with _gen_mu:
        gens = list(_recent_gens)
    return gens[-limit:]


def generations_snapshot(limit: int = 50) -> dict:
    """The /serving/generations page data: aggregate TTFT/ITL
    percentiles from the global recorders, prefill-skip over the recent
    window, supervisor recovery counts, and the recent records
    themselves (newest last)."""
    from brpc_tpu.serving.engine import ITL_REC, TTFT_REC
    recent = recent_generations(limit)
    # skip-ratio over ENGINE attempt records only (they carry
    # prefix_hit); supervisor rows describe the same generations again
    # and would double-count every prompt in the denominator
    prompt = sum(r["prompt_len"] for r in recent if "prefix_hit" in r)
    hit = sum(r["prefix_hit"] for r in recent if "prefix_hit" in r)
    with _reg_mu:
        supervisors = dict(_supervisors)
    recoveries = sum(s.restarts_total.get_value()
                     for s in supervisors.values())
    # speculative-decoding acceptance over the recent window (ISSUE
    # 11): engine records carry per-generation accept_rate /
    # tokens_per_step when a draft proposer ran
    spec_rows = [r for r in recent if "accept_rate" in r]
    proposed = sum(r.get("spec_proposed", 0) for r in spec_rows)
    accepted = sum(r.get("spec_accepted", 0) for r in spec_rows)
    speculative = {
        "generations": len(spec_rows),
        "accept_rate": round(accepted / proposed, 4) if proposed
        else 0.0,
        "avg_tokens_per_step": round(
            sum(r["tokens_per_step"] for r in spec_rows)
            / len(spec_rows), 2) if spec_rows else 0.0,
    }
    return {
        "aggregates": {
            "speculative": speculative,
            "ttft_us": {
                "count": TTFT_REC.count(),
                "avg": round(TTFT_REC.latency(), 1),
                "p50": round(TTFT_REC.latency_percentile(0.5), 1),
                "p99": round(TTFT_REC.latency_percentile(0.99), 1),
            },
            "itl_us": {
                "count": ITL_REC.count(),
                "avg": round(ITL_REC.latency(), 1),
                "p50": round(ITL_REC.latency_percentile(0.5), 1),
                "p99": round(ITL_REC.latency_percentile(0.99), 1),
            },
            "prefill_skip_ratio": round(hit / prompt, 4) if prompt else 0.0,
            "recoveries": recoveries,
        },
        "recent": recent,
    }


from brpc_tpu.serving.batcher import DynamicBatcher  # noqa: E402,F401
from brpc_tpu.serving.engine import DecodeEngine, MessageSink  # noqa: E402,F401
from brpc_tpu.serving.service import (  # noqa: E402,F401
    ScoreClient, ServingService, http_generate_handler, register_serving,
)
from brpc_tpu.serving.supervisor import EngineSupervisor  # noqa: E402,F401
from brpc_tpu.serving.ladder import OverloadLadder  # noqa: E402,F401
from brpc_tpu.serving.speculative import (  # noqa: E402,F401
    DraftModelProposer, DraftProposer, NGramProposer, as_proposer,
)
from brpc_tpu.serving.router import (  # noqa: E402,F401
    ClusterRouter, ReplicaHandle, RouterClient, RouterService,
    SessionTable, register_router,
)
from brpc_tpu.serving.session_wal import SessionWAL  # noqa: E402,F401
from brpc_tpu.serving.cluster_control import (  # noqa: E402,F401
    CLUSTER_SERVICE, ClusterControlService, register_cluster_control,
)
from brpc_tpu.serving.modelplane import (  # noqa: E402,F401
    DEFAULT_MODEL, CanarySplit, ModelCatalog, ModelMetrics,
    ReplicaDeployments, cluster_deploy, deployment_key,
    model_fingerprint, split_deployment_key,
)
from brpc_tpu.serving.telemetry import (  # noqa: E402,F401
    TELEMETRY_SERVICE, FleetCollector, TelemetryService,
    register_telemetry, telemetry_snapshot,
)
from brpc_tpu.serving.slo import Objective, SLOEngine  # noqa: E402,F401
