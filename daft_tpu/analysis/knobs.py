"""The engine's single declarative knob registry.

Every ``DAFT_TPU_*`` environment knob is declared here exactly once:
name, parse type, default, owning module, README table group, and a
one-line doc. Runtime code reads knobs through the typed accessors
(``env_int`` / ``env_float`` / ``env_bool`` / ``env_bytes`` /
``env_str`` / ``env_raw``) so each knob has exactly ONE parse site —
``rule_knobs`` flags direct ``os.environ`` reads of ``DAFT_TPU_*``
names anywhere else, and the README knob tables are *generated* from
this registry (``python -m daft_tpu.analysis --knob-docs``), so code,
config and docs cannot drift silently.

Knobs mirrored by an ``ExecutionConfig`` field record it in
``config_field``; for those the env var is the per-process override and
the config field is the per-query value (``context._exec_config_from_env``
parses the same spelling — the registry documents both).

This module must stay import-light (os + dataclasses only): the whole
engine imports it.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

_FALSY = ("0", "false", "False", "no", "off", "")


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str           # full env var name (DAFT_TPU_…)
    type: str           # "int" | "float" | "bool" | "str" | "bytes"
    default: object     # parsed-type default; None = unset/contextual
    module: str         # owning module (repo-relative path)
    group: str          # README table group (one generated table each)
    doc: str            # one-line effect description for the table
    config_field: str = ""   # mirrored ExecutionConfig field, if any
    default_str: str = ""    # display override for the docs table


def _k(name, type_, default, module, group, doc, config_field="",
       default_str=""):
    return Knob(name, type_, default, module, group, doc, config_field,
                default_str)


_KNOBS: List[Knob] = [
    # ---------------------------------------------------------- core
    _k("DAFT_TPU_DEVICE", "bool", True, "daft_tpu/device/runtime.py",
       "core", "`0` disables the device tier entirely (pure host execution)"),
    _k("DAFT_TPU_DEVICE_FORCE", "str", None, "daft_tpu/device/costmodel.py",
       "core", "force device-vs-host routing: `1`/`device` forces device, "
       "`0`/`host` forces host; unset lets the measured-link cost model "
       "decide"),
    _k("DAFT_TPU_DEVICE_MIN_ROWS", "int", None, "daft_tpu/device/runtime.py",
       "core", "row floor below which ops stay on host (default: 4096 on a "
       "transfer-bound link, 0 when the backend shares host memory)",
       default_str="auto"),
    _k("DAFT_TPU_DEVICE_JOIN", "str", None, "daft_tpu/joins.py",
       "core", "`1`/`0` force-overrides the cost model's device-join "
       "routing; unset = modeled", default_str="auto"),
    _k("DAFT_TPU_DEVICE_INFLIGHT", "int", 2,
       "daft_tpu/device/pipeline.py", "core",
       "in-flight device pipeline slots: morsel N+1's host encode/upload "
       "overlaps morsel N's device compute and morsel N−1's "
       "download/decode; `0` = synchronous dispatch (forced under "
       "`DAFT_TPU_CHAOS_SERIALIZE=1` or an active fault plan)",
       config_field="tpu_device_inflight"),
    _k("DAFT_TPU_NATIVE", "bool", True, "daft_tpu/native/__init__.py",
       "core", "`0` disables the native (C-accelerated) expression paths"),
    _k("DAFT_TPU_ACTOR_POOL", "bool", True, "daft_tpu/actor_pool.py",
       "core", "`0` disables the stateful-UDF actor pool (inline execution)"),
    _k("DAFT_TPU_MEMORY_LIMIT", "bytes", None, "daft_tpu/execution/memory.py",
       "core", "process memory budget for scan admission + spill decisions "
       "(accepts byte suffixes, e.g. `64GiB`); unset = no budget"),
    _k("DAFT_TPU_SPILL_DIR", "str", None, "daft_tpu/execution/memory.py",
       "core", "spill directory root (default: a fresh "
       "`daft_tpu_spill_<pid>` under the system tmpdir)",
       default_str="tmpdir"),
    _k("DAFT_TPU_MESH_DEVICES", "int", None, "daft_tpu/parallel/mesh.py",
       "core", "caps the device-mesh axis length (default: all visible "
       "devices)", default_str="all"),
    _k("DAFT_TPU_MESH_MIN_ROWS", "int", None, "daft_tpu/parallel/mesh.py",
       "core", "force-override for mesh (multi-chip collective) admission: "
       "`0` forces the mesh path, `N` requires ≥N rows; unset lets the "
       "cost model price the collective from the calibrated ICI link rate "
       "(`DAFT_TPU_ICI_MBPS`)", default_str="cost model"),
    _k("DAFT_TPU_REAL_DEVICE", "bool", False, "tests/conftest.py",
       "core", "`1` runs the test suite against the real accelerator "
       "backend (no CPU forcing, no virtual mesh)"),
    # -------------------------------------------------------- device
    _k("DAFT_TPU_BACKEND_TIMEOUT", "float", 60.0,
       "daft_tpu/device/backend.py", "device",
       "seconds to wait for device-backend initialization before falling "
       "back to host"),
    _k("DAFT_TPU_SIZE_CLASSES", "str", "pow2", "daft_tpu/device/column.py",
       "device", "size-class ladder batches pad to: `pow2` (default), "
       "`pow4` (coarser: 4x steps, fewer distinct programs, more "
       "padding), or an explicit comma list of capacities (e.g. "
       "`1024,65536,1048576`); above the ladder top, capacities keep "
       "doubling", config_field="tpu_size_classes"),
    _k("DAFT_TPU_AOT_WARMUP", "bool", False, "daft_tpu/device/warmup.py",
       "device", "`1` AOT-compiles (`jit(...).lower().compile()`) the "
       "device kernel library — and any already-compiled fused "
       "fragments — over the size-class grid at serving "
       "startup, so first queries re-enter warm programs; the "
       "persistent compile cache (`JAX_COMPILATION_CACHE_DIR`, else "
       "`<repo>/.cache/jax` off-CPU) makes them survive restarts",
       config_field="tpu_aot_warmup"),
    _k("DAFT_TPU_FUSION", "str", "auto", "daft_tpu/physical/fusion.py",
       "device", "whole-query fusion regions (round 21) and the scan's "
       "selection over resident columns (`executor._scan_select`): `auto` "
       "lets the cost model price each region (`costmodel.fusion_wins`) "
       "and each filtered scan's table (`costmodel.select_wins`), `1` "
       "force-admits every planned region and every table the selection "
       "program can take, `0` disables the planner pass entirely and "
       "keeps every filtered scan with the reader",
       config_field="tpu_fusion"),
    _k("DAFT_TPU_FUSION_MAX_OPS", "int", 8, "daft_tpu/physical/fusion.py",
       "device", "region-size cap: the planner stops growing a fusion "
       "region past this many fused operators (bounds trace size and "
       "retrace surface)", config_field="tpu_fusion_max_ops"),
    _k("DAFT_TPU_HBM_CACHE_BYTES", "bytes", 8 * 1024 ** 3,
       "daft_tpu/device/cache.py", "device",
       "HBM budget for the resident-column cache (byte suffixes accepted)",
       default_str="8GiB"),
    _k("DAFT_TPU_LINK_RTT_MS", "float", None, "daft_tpu/device/costmodel.py",
       "device", "override the measured host↔device link RTT (ms)",
       default_str="measured"),
    _k("DAFT_TPU_LINK_UP_MBPS", "float", None,
       "daft_tpu/device/costmodel.py", "device",
       "override the measured host→device bandwidth (MB/s)",
       default_str="measured"),
    _k("DAFT_TPU_LINK_DOWN_MBPS", "float", None,
       "daft_tpu/device/costmodel.py", "device",
       "override the measured device→host bandwidth (MB/s)",
       default_str="measured"),
    _k("DAFT_TPU_LINK_CACHE", "bool", True, "daft_tpu/device/costmodel.py",
       "device", "`0` disables the persisted link-calibration profile "
       "(re-measures per process)"),
    _k("DAFT_TPU_LINK_CACHE_PATH", "str", None,
       "daft_tpu/device/costmodel.py", "device",
       "path of the persisted link profile (default: under the user cache "
       "dir)", default_str="auto"),
    _k("DAFT_TPU_DISPATCH_LOG", "str", None, "daft_tpu/device/costmodel.py",
       "device", "JSONL path appending one record per real device dispatch"),
    _k("DAFT_TPU_CACHE_INVEST", "bool", True,
       "daft_tpu/device/costmodel.py", "device",
       "`0` stops the cost model from pricing upload as an investment for "
       "cacheable (reused) columns"),
    # ------------------------------------------------------- shuffle
    _k("DAFT_TPU_DISTRIBUTED_SHUFFLE", "str", "flight",
       "daft_tpu/distributed/scheduler.py", "shuffle",
       "`driver` routes stage boundaries through the driver instead of "
       "the worker-to-worker shuffle plane"),
    _k("DAFT_TPU_SHUFFLE_TRANSPORT", "str", "flight",
       "daft_tpu/distributed/shuffle_service.py", "shuffle",
       "`flight` (Arrow Flight) or `http` partition transport"),
    _k("DAFT_TPU_SHUFFLE_HOST", "str", "127.0.0.1",
       "daft_tpu/distributed/shuffle_service.py", "shuffle",
       "bind address of the per-host partition server (`0.0.0.0` serves "
       "other hosts)"),
    _k("DAFT_TPU_SHUFFLE_ADVERTISE", "str", None,
       "daft_tpu/distributed/shuffle_service.py", "shuffle",
       "address peers are told to fetch from (default: the bind host, or "
       "`127.0.0.1` when bound to `0.0.0.0`)", default_str="bind host"),
    _k("DAFT_TPU_SHUFFLE_COMPRESSION", "str", "lz4",
       "daft_tpu/distributed/shuffle_service.py", "shuffle",
       "`lz4`/`zstd`/`none` IPC buffer compression for shuffle spill+wire; "
       "auto-falls back to `none` when the codec is missing from the "
       "pyarrow build"),
    _k("DAFT_TPU_SHUFFLE_FETCH_PARALLELISM", "int", 4,
       "daft_tpu/distributed/worker.py", "shuffle",
       "bounded per-source fetch concurrency for a reduce task's stage "
       "input; `DAFT_TPU_CHAOS_SERIALIZE=1` forces 1, and an active "
       "`DAFT_TPU_FAULT_SPEC` defaults it to 1 (set explicitly to combine)"),
    _k("DAFT_TPU_SHUFFLE_COMBINE", "str", "auto",
       "daft_tpu/distributed/scheduler.py", "shuffle",
       "map-side combine: `auto` (cost-model gated), `1` force, `0` "
       "escape hatch"),
    _k("DAFT_TPU_SHUFFLE_WIRE_MBPS", "float", 1000.0,
       "daft_tpu/device/costmodel.py", "shuffle",
       "wire bandwidth the combine and exchange-path cost models assume "
       "(set to the pod's real DCN number)"),
    _k("DAFT_TPU_ICI_MBPS", "float", None,
       "daft_tpu/device/costmodel.py", "shuffle",
       "override the measured intra-mesh (ICI) collective bandwidth "
       "(MB/s) the mesh-admission and exchange-path cost models price "
       "against", default_str="measured"),
    _k("DAFT_TPU_WORKER_TOPOLOGY", "str", None,
       "daft_tpu/distributed/topology.py", "shuffle",
       "mesh-group spec `name=w0,w1;name2=w2` naming which workers share "
       "a device mesh (pod/host); unset autodetects — all in-process "
       "workers share the process mesh when one is up, else every worker "
       "is its own group (Flight-only)",
       config_field="tpu_worker_topology", default_str="autodetect"),
    _k("DAFT_TPU_EXCHANGE_PATH", "str", "auto",
       "daft_tpu/distributed/topology.py", "shuffle",
       "hash-boundary exchange path: `collective` (intra-mesh ICI "
       "all_to_all), `hierarchical` (intra-mesh collective + one Flight "
       "stream per mesh), `flight` (per-worker streams), or `auto` "
       "(topology + cost model decide; chaos serialize forces `flight`)",
       config_field="tpu_exchange_path"),
    _k("DAFT_TPU_SHUFFLE_TIMEOUT", "float", 600.0,
       "daft_tpu/distributed/shuffle_service.py", "shuffle",
       "seconds a partition fetch may take before it fails as retryable"),
    _k("DAFT_TPU_SHUFFLE_TTL", "float", 86400.0,
       "daft_tpu/distributed/shuffle_service.py", "shuffle",
       "idle seconds before an orphaned shuffle directory is swept at "
       "service startup"),
    # ---------------------------------------------------- resilience
    _k("DAFT_TPU_FAULT_SPEC", "str", None,
       "daft_tpu/distributed/resilience.py", "resilience",
       "comma-separated `site:rate[:N][:sticky]` seeded fault-injection "
       "spec (`task`/`fetch`/`crash`/`rpc` sites)"),
    _k("DAFT_TPU_FAULT_SEED", "str", "0",
       "daft_tpu/distributed/resilience.py", "resilience",
       "seed hashed into every fault-injection decision (same seed → "
       "bit-identical chaos replay)"),
    _k("DAFT_TPU_CHAOS_SERIALIZE", "bool", False,
       "daft_tpu/distributed/worker.py", "resilience",
       "`1` serializes task execution (one task with all its retries at a "
       "time) and degrades the fetch/scan fast paths so chaos runs replay "
       "bit-identically"),
    _k("DAFT_TPU_MAX_RETRIES", "int", 3,
       "daft_tpu/distributed/resilience.py", "resilience",
       "bounded per-task retry budget"),
    _k("DAFT_TPU_RETRY_BACKOFF", "float", 0.05,
       "daft_tpu/distributed/resilience.py", "resilience",
       "retry backoff base seconds (deterministic jitter on top)"),
    _k("DAFT_TPU_RETRY_BACKOFF_CAP", "float", 2.0,
       "daft_tpu/distributed/resilience.py", "resilience",
       "retry backoff cap seconds"),
    _k("DAFT_TPU_QUARANTINE_AFTER", "int", 3,
       "daft_tpu/distributed/resilience.py", "resilience",
       "consecutive failures that quarantine a worker"),
    _k("DAFT_TPU_QUARANTINE_S", "float", 30.0,
       "daft_tpu/distributed/resilience.py", "resilience",
       "quarantine duration seconds (timed re-admission, never empty "
       "placement)"),
    _k("DAFT_TPU_TASK_TIMEOUT", "float", 0.0,
       "daft_tpu/distributed/resilience.py", "resilience",
       "seconds before a hung task attempt is abandoned as retryable "
       "(`0` = off)"),
    _k("DAFT_TPU_SPECULATIVE_MULTIPLIER", "float", 4.0,
       "daft_tpu/distributed/resilience.py", "resilience",
       "speculative-execution trigger: multiplier × median sibling "
       "duration (`0` = off)"),
    _k("DAFT_TPU_SPECULATIVE_MIN_S", "float", 0.5,
       "daft_tpu/distributed/resilience.py", "resilience",
       "minimum task age before speculation is considered"),
    _k("DAFT_TPU_WORKER_TIMEOUT", "float", 3600.0,
       "daft_tpu/distributed/remote_worker.py", "resilience",
       "remote-worker RPC timeout seconds"),
    _k("DAFT_TPU_NUM_WORKERS", "int", 0,
       "daft_tpu/runners/distributed_runner.py", "resilience",
       "distributed-runner worker count (`0` = auto from cpu count)",
       default_str="auto"),
    # --------------------------------------------------------- spill
    _k("DAFT_TPU_SPILL_JOIN", "str", "auto",
       "daft_tpu/execution/out_of_core.py", "spill",
       "grace hash join gate: `auto` (cost-model priced via "
       "`spill_plan_wins`), `1` forces partitioned execution, `0` "
       "restores the legacy materialize-then-refan join (no recursion)",
       config_field="tpu_spill_join"),
    _k("DAFT_TPU_SPILL_AGG", "str", "auto",
       "daft_tpu/execution/out_of_core.py", "spill",
       "spill-partitioned aggregation gate: `auto` spills the fused "
       "reducer's group state only when the budget can't hold it, `1` "
       "forces the spilling reducer, `0` declines the fusion for "
       "over-budget states (legacy exchange plan)",
       config_field="tpu_spill_agg"),
    _k("DAFT_TPU_SPILL_PARTITIONS", "int", 0,
       "daft_tpu/execution/out_of_core.py", "spill",
       "forces the first-level radix fanout of grace joins and spilling "
       "reducers; `0` lets planner size/NDV evidence pick the count",
       config_field="tpu_spill_partitions", default_str="evidence"),
    _k("DAFT_TPU_SPILL_MAX_DEPTH", "int", 3,
       "daft_tpu/execution/out_of_core.py", "spill",
       "rotated-radix recursion bound for a bucket that still exceeds "
       "its budget; exhaustion (an unsplittable all-duplicate key) falls "
       "through to an in-memory merge, counted in `depth_exhausted`",
       config_field="tpu_spill_max_depth"),
    _k("DAFT_TPU_SPILL_COMPRESSION", "str", None,
       "daft_tpu/execution/memory.py", "spill",
       "spill-file Arrow IPC buffer codec: `lz4` | `zstd` | `none`; "
       "unset inherits the shuffle plane's "
       "`DAFT_TPU_SHUFFLE_COMPRESSION` (default `lz4`); readers are "
       "self-describing, so mixed-codec spill dirs always read back",
       config_field="tpu_spill_compression", default_str="inherit"),
    _k("DAFT_TPU_SPILL_IO_PARALLELISM", "int", 4,
       "daft_tpu/execution/spill_io.py", "spill",
       "concurrent spill write/read tasks on the bounded spill-IO pool "
       "(writes chain per bucket, so push order is preserved); `0` "
       "restores the serial r19 path, which chaos serialize / an active "
       "fault plan also force", config_field="tpu_spill_io_parallelism"),
    _k("DAFT_TPU_GOVERNOR", "bool", True,
       "daft_tpu/execution/governor.py", "spill",
       "`0` disables the memory governor (RSS-watermark backpressure: "
       "budget/prefetch shrinks + bounded throttles); inert anyway "
       "without `DAFT_TPU_MEMORY_LIMIT` or under the chaos freeze"),
    _k("DAFT_TPU_GOVERNOR_HIGH", "float", 0.85,
       "daft_tpu/execution/governor.py", "spill",
       "RSS fraction of the memory limit that enters the pressured "
       "state (governor actions engage)",
       config_field="tpu_governor_high"),
    _k("DAFT_TPU_GOVERNOR_LOW", "float", 0.70,
       "daft_tpu/execution/governor.py", "spill",
       "RSS fraction of the memory limit that clears the pressured "
       "state — the hysteresis floor, clamped below the high watermark",
       config_field="tpu_governor_low"),
    # ------------------------------------------------------- io-scan
    _k("DAFT_TPU_IO_COALESCE_GAP", "bytes", 1 << 20,
       "daft_tpu/io/read_planner.py", "io-scan",
       "hole tolerance when coalescing needed byte ranges into requests",
       config_field="tpu_io_coalesce_gap", default_str="1MiB"),
    _k("DAFT_TPU_IO_MIN_REQUEST", "bytes", 8 << 20,
       "daft_tpu/io/read_planner.py", "io-scan",
       "request-size floor: sub-floor requests absorb neighbors across "
       "holes smaller than the floor",
       config_field="tpu_io_min_request", default_str="8MiB"),
    _k("DAFT_TPU_IO_RANGE_PARALLELISM", "int", 8,
       "daft_tpu/io/read_planner.py", "io-scan",
       "concurrent range GETs per source (capped by the source's "
       "`max_connections`)", config_field="tpu_io_range_parallelism"),
    _k("DAFT_TPU_IO_PLANNED_READS", "bool", True,
       "daft_tpu/io/read_planner.py", "io-scan",
       "`0` restores the naive per-column-chunk ranged-read path",
       config_field="tpu_io_planned_reads", default_str="1"),
    _k("DAFT_TPU_SCAN_PREFETCH", "int", 2,
       "daft_tpu/io/read_planner.py", "io-scan",
       "ScanTasks resolved ahead of the consumer; `0` disables; "
       "chaos/fault plans force the sequential path",
       config_field="tpu_scan_prefetch"),
    _k("DAFT_TPU_IO_STREAM_CHUNK", "bytes", 8 << 20,
       "daft_tpu/io/read_planner.py", "io-scan",
       "chunk size for streaming remote CSV/JSON reads",
       default_str="8MiB"),
    _k("DAFT_TPU_IO_INFER_BYTES", "bytes", 1 << 20,
       "daft_tpu/io/read_planner.py", "io-scan",
       "head-range budget for remote CSV/JSON schema inference (`0` → "
       "whole object)", default_str="1MiB"),
    # ------------------------------------------------------- serving
    _k("DAFT_TPU_SERVE_CONCURRENCY", "int", 4,
       "daft_tpu/serving/scheduler.py", "serving",
       "worker slots in the query scheduler (concurrently RUNNING "
       "queries)", config_field="tpu_serve_concurrency"),
    _k("DAFT_TPU_SERVE_QUEUE_DEPTH", "int", 64,
       "daft_tpu/serving/scheduler.py", "serving",
       "max queued (not yet running) queries before submissions are "
       "rejected `queue_full`", config_field="tpu_serve_queue_depth"),
    _k("DAFT_TPU_SERVE_QUEUE_TIMEOUT", "float", 30.0,
       "daft_tpu/serving/scheduler.py", "serving",
       "seconds a query may wait (in queue, then again in admission) "
       "before it is rejected `queue_timeout`; `0` waits forever",
       config_field="tpu_serve_queue_timeout"),
    _k("DAFT_TPU_SERVE_PLAN_CACHE_BYTES", "bytes", 64 << 20,
       "daft_tpu/serving/scheduler.py", "serving",
       "LRU budget for the compiled-plan cache (optimized+translated "
       "physical plans keyed by plan fingerprint); `0` disables",
       config_field="tpu_serve_plan_cache_bytes", default_str="64MiB"),
    _k("DAFT_TPU_SERVE_RESULT_CACHE_BYTES", "bytes", 64 << 20,
       "daft_tpu/serving/scheduler.py", "serving",
       "LRU budget for the result cache (materialized PartitionSets for "
       "identical literal-inclusive fingerprints over unchanged "
       "sources); `0` disables",
       config_field="tpu_serve_result_cache_bytes", default_str="64MiB"),
    _k("DAFT_TPU_SERVE_MEMORY", "bytes", None,
       "daft_tpu/serving/scheduler.py", "serving",
       "admission-control byte budget shared by concurrent queries "
       "(default: `DAFT_TPU_MEMORY_LIMIT`, else the breaker budget; "
       "`0` disables admission)", default_str="memory limit"),
    _k("DAFT_TPU_SERVE_OP_TTL", "float", 600.0,
       "daft_tpu/connect/server.py", "serving",
       "seconds a FINISHED reattachable Spark Connect operation retains "
       "its response buffer before the sweep drops it; `0` disables"),
    _k("DAFT_TPU_SERVE_OP_RETAIN_BYTES", "bytes", 64 << 20,
       "daft_tpu/connect/server.py", "serving",
       "per-session retained-response budget across finished "
       "operations (newest kept first); `0` disables",
       default_str="64MiB"),
    # -------------------------------------------------------- fleet
    _k("DAFT_TPU_FLEET_VNODES", "int", 64,
       "daft_tpu/fleet/router.py", "fleet",
       "virtual nodes per replica on the consistent-hash session ring "
       "(more vnodes = smoother session spread, larger ring)",
       config_field="tpu_fleet_vnodes"),
    _k("DAFT_TPU_FLEET_GOSSIP_S", "float", 2.0,
       "daft_tpu/fleet/replica.py", "fleet",
       "seconds between anti-entropy gossip rounds republishing this "
       "replica's learned state (calibration profile + admission "
       "history) to every peer; floored at `0.05`",
       config_field="tpu_fleet_gossip_s"),
    _k("DAFT_TPU_FLEET_DRAIN_TIMEOUT", "float", 10.0,
       "daft_tpu/fleet/router.py", "fleet",
       "seconds a draining replica may finish in-flight queries before "
       "the router cancels the stragglers and re-homes its sessions",
       config_field="tpu_fleet_drain_timeout"),
    _k("DAFT_TPU_FLEET_SIDECAR", "str", None,
       "daft_tpu/fleet/cache_tier.py", "fleet",
       "`host:port` of a fleet cache sidecar (`python -m "
       "daft_tpu.fleet.cache_tier --port N`); when set, replicas consult "
       "it for cross-process result-cache hits", default_str="off"),
    _k("DAFT_TPU_FLEET_SIDECAR_BYTES", "bytes", 256 << 20,
       "daft_tpu/fleet/cache_tier.py", "fleet",
       "LRU byte budget of the cache sidecar's blob store",
       default_str="256MiB"),
    _k("DAFT_TPU_FLEET_PEERS", "str", None,
       "daft_tpu/fleet/replica.py", "fleet",
       "comma-separated control addresses (`host:port`) of the peer "
       "replicas this one gossips with", default_str="none"),
    _k("DAFT_TPU_FLEET_REPLICA_ID", "str", None,
       "daft_tpu/fleet/replica.py", "fleet",
       "stable identity of this replica process (its gossip origin); "
       "`--replica-id` overrides", default_str="replica-0"),
    # ------------------------------------------------------ adaptive
    _k("DAFT_TPU_ADAPTIVE", "bool", False,
       "daft_tpu/distributed/replan.py", "adaptive",
       "`1` enables distributed runtime re-planning: boundary actuals "
       "(exact rows/bytes/NDV from map receipts and in-memory sources) "
       "rewrite downstream fragment estimates and re-pick combine "
       "gating, broadcast demotion, exchange rung and spill fanout "
       "before each stage dispatches; chaos-serialize or an active "
       "fault plan disables it (counted `replan_frozen`)",
       config_field="tpu_adaptive"),
    _k("DAFT_TPU_ADAPTIVE_HISTORY", "int", 512,
       "daft_tpu/physical/adaptive.py", "adaptive",
       "bound on the AdaptivePlanner decision history; appends past the "
       "cap evict the oldest entry (counted `history_evictions`)",
       config_field="tpu_adaptive_history"),
    _k("DAFT_TPU_CALIBRATION", "bool", False,
       "daft_tpu/device/calibration.py", "adaptive",
       "`1` enables the calibrated cost-model profile: observed "
       "`DEV_*` kernel rates, shuffle wire rate, ICI rate and the "
       "footer-NDV ratio override the hard-coded constants once the "
       "sample floor is met; frozen (defaults + no observations) under "
       "chaos-serialize or an active fault plan",
       config_field="tpu_calibration"),
    _k("DAFT_TPU_CALIBRATION_DIR", "str", None,
       "daft_tpu/device/calibration.py", "adaptive",
       "directory persisting one calibration profile per backend "
       "(`calibration_<backend>.json`, atomic rewrite); unset keeps the "
       "profile in-memory for the process lifetime",
       config_field="tpu_calibration_dir", default_str="in-memory"),
    _k("DAFT_TPU_CALIBRATION_ALPHA", "float", 0.2,
       "daft_tpu/device/calibration.py", "adaptive",
       "EWMA weight of one calibration observation (weighted samples "
       "collapse to one update; clamped to (0, 1])",
       config_field="tpu_calibration_alpha"),
    _k("DAFT_TPU_CALIBRATION_MIN_SAMPLES", "int", 8,
       "daft_tpu/device/calibration.py", "adaptive",
       "sample-count floor a learned constant needs before it overrides "
       "the hard-coded default",
       config_field="tpu_calibration_min_samples"),
    # ------------------------------------------------- observability
    _k("DAFT_TPU_XPLANE_DIR", "str", None, "daft_tpu/observability.py",
       "observability", "directory capturing a jax profiler "
       "(xplane/TensorBoard) trace per query; the query is traced and its "
       "spans lie in the profile as `daft:<span>`"),
    _k("DAFT_TPU_PROGRESS", "bool", False, "daft_tpu/observability.py",
       "observability", "`1` enables a tqdm partition-progress bar"),
    _k("DAFT_TPU_OTLP_ENDPOINT", "str", None, "daft_tpu/observability.py",
       "observability", "OTLP/HTTP collector endpoint receiving per-query "
       "operator counters"),
    _k("DAFT_TPU_SANITIZE", "bool", False,
       "daft_tpu/analysis/lock_sanitizer.py", "observability",
       "`1` wraps engine lock acquisition in the runtime lock-order "
       "sanitizer (cycle detection, contention + blocking-while-held "
       "accounting; reported at pytest session end and in "
       "`explain(analyze=True)`)"),
    _k("DAFT_TPU_SANITIZE_RETRACE", "int", 0,
       "daft_tpu/analysis/retrace_sanitizer.py", "observability",
       "with `DAFT_TPU_SANITIZE=1`: arms the runtime retrace sanitizer "
       "— JAX trace events are charged against each registered dispatch "
       "site's per-signature budget x this multiplier; budget "
       "violations fail the pytest session; `0` = off (no listener, "
       "allocation-free scopes)"),
    _k("DAFT_TPU_SANITIZE_PLAN", "bool", False,
       "daft_tpu/analysis/plan_sanitizer.py", "observability",
       "`1` arms the runtime plan sanitizer: root-schema equality after "
       "every optimizer rule application, sampled hash-partition "
       "membership re-verification at exchange/spill boundaries, sort-"
       "order checks after Sort/TopN, and row-count conservation where "
       "the plan-contract registry declares it; violations fail the "
       "pytest session and surface in `explain(analyze=True)`, the "
       "flight recorder, and `/metrics`",
       config_field="tpu_sanitize_plan"),
    _k("DAFT_TPU_SANITIZE_PLAN_SAMPLE", "int", 64,
       "daft_tpu/analysis/plan_sanitizer.py", "observability",
       "rows sampled per boundary partition for the plan sanitizer's "
       "membership/order re-verification (higher = stronger checks, "
       "more re-hash work)",
       config_field="tpu_sanitize_plan_sample"),
    _k("DAFT_TPU_FUZZ_SEED", "int", 0,
       "daft_tpu/analysis/plan_fuzzer.py", "observability",
       "base seed of the differential plan fuzzer (`python -m "
       "daft_tpu.analysis --fuzz`); seed i of a run derives "
       "deterministically from it",
       config_field="tpu_fuzz_seed"),
    _k("DAFT_TPU_FUZZ_COUNT", "int", 50,
       "daft_tpu/analysis/plan_fuzzer.py", "observability",
       "how many fuzzer seeds a `--fuzz` run executes (each seed runs "
       "the full engine-mode matrix and compares answers bit-for-bit)",
       config_field="tpu_fuzz_count"),
    _k("DAFT_TPU_TRACE", "bool", False, "daft_tpu/tracing.py",
       "observability", "`1` enables the query-wide tracing plane: one "
       "span tree per query across scheduler/planner/device/pipeline/"
       "distributed layers, exported as Chrome trace JSON + OTLP spans"),
    _k("DAFT_TPU_TRACE_SAMPLE", "float", 1.0, "daft_tpu/tracing.py",
       "observability", "fraction of queries traced when tracing is on "
       "(deterministic per-query decision hashed from the trace key, "
       "never RNG)"),
    _k("DAFT_TPU_TRACE_DIR", "str", None, "daft_tpu/tracing.py",
       "observability", "directory receiving one perfetto-loadable "
       "`trace_<id>.json` per traced query (unset: traces stay "
       "in-memory for OTLP/flight-recorder export only)"),
    _k("DAFT_TPU_TRACE_MAX_SPANS", "int", 8192, "daft_tpu/tracing.py",
       "observability", "per-query span-buffer bound; spans past it are "
       "counted as dropped, never allocated"),
    _k("DAFT_TPU_OTLP_TIMEOUT", "float", 5.0, "daft_tpu/observability.py",
       "observability", "seconds an OTLP/HTTP export POST may take; a "
       "hung or failing collector is counted in `otlp_export_errors` "
       "and never stalls or fails the query"),
    _k("DAFT_TPU_QUERY_LOG", "str", None, "daft_tpu/tracing.py",
       "observability", "flight-recorder JSONL path persisting every "
       "query's stat blocks + trace summary + slow-query flag "
       "(size-capped rotation; served at `/api/history`)"),
    _k("DAFT_TPU_QUERY_LOG_BYTES", "bytes", 16 << 20,
       "daft_tpu/tracing.py", "observability",
       "flight-recorder rotation cap: when the JSONL exceeds it, it "
       "rotates to `<path>.1` (one generation kept)",
       default_str="16MiB"),
    _k("DAFT_TPU_SLOW_QUERY_MS", "float", 0.0, "daft_tpu/tracing.py",
       "observability", "wall-time threshold flagging a flight-recorder "
       "entry `slow: true` (`0` disables the flag)"),
]

REGISTRY: Dict[str, Knob] = {k.name: k for k in _KNOBS}

GROUPS: List[str] = []
for _kn in _KNOBS:
    if _kn.group not in GROUPS:
        GROUPS.append(_kn.group)


class UnknownKnobError(KeyError):
    pass


def _checked(name: str, expect_type: Optional[str] = None) -> Knob:
    k = REGISTRY.get(name)
    if k is None:
        raise UnknownKnobError(
            f"{name} is not in the knob registry "
            f"(daft_tpu/analysis/knobs.py) — register it before reading it")
    if expect_type is not None and k.type != expect_type:
        raise TypeError(
            f"{name} is registered as type {k.type!r} but was read as "
            f"{expect_type!r} — one knob, one parse")
    return k


def parse(name: str, raw: str):
    """Parse a raw env string per the knob's registered type."""
    k = _checked(name)
    if k.type == "int":
        return int(raw)
    if k.type == "float":
        return float(raw)
    if k.type == "bool":
        return raw not in _FALSY
    if k.type == "bytes":
        from ..execution.memory import parse_bytes
        return parse_bytes(raw)
    return raw


_MISSING = object()


def _get(name: str, type_: str, default):
    k = _checked(name, type_)
    v = os.environ.get(name)
    if v is None or v == "":
        return k.default if default is _MISSING else default
    return parse(name, v)


def env_raw(name: str) -> Optional[str]:
    """The raw env string, or None when unset/empty. For sites whose
    semantics hinge on *presence* (tri-state force flags)."""
    _checked(name)
    v = os.environ.get(name)
    return None if v is None or v == "" else v


def env_is_set(name: str) -> bool:
    _checked(name)
    return os.environ.get(name) is not None


def env_int(name: str, default=_MISSING) -> Optional[int]:
    return _get(name, "int", default)


def env_float(name: str, default=_MISSING) -> Optional[float]:
    return _get(name, "float", default)


def env_bool(name: str, default=_MISSING) -> Optional[bool]:
    return _get(name, "bool", default)


def env_bytes(name: str, default=_MISSING) -> Optional[int]:
    return _get(name, "bytes", default)


def env_str(name: str, default=_MISSING) -> Optional[str]:
    return _get(name, "str", default)


# ------------------------------------------------------------------ docs

_TABLE_HEADER = "| env var | type | default | effect |\n| --- | --- | --- | --- |"


def _default_cell(k: Knob) -> str:
    if k.default_str:
        return f"`{k.default_str}`"
    if k.default is None:
        return "unset"
    if k.type == "bool":
        return "`1`" if k.default else "`0`"
    return f"`{k.default}`"


def knob_table_markdown(group: str) -> str:
    """One generated markdown table for a registry group."""
    rows = [_TABLE_HEADER]
    for k in _KNOBS:
        if k.group != group:
            continue
        doc = k.doc
        if k.config_field:
            doc += f" (mirrors `ExecutionConfig.{k.config_field}`)"
        rows.append(f"| `{k.name}` | {k.type} | {_default_cell(k)} | {doc} |")
    return "\n".join(rows)


def _marker(group: str, end: bool) -> str:
    word = "END" if end else "BEGIN"
    return f"<!-- knob-table:{group} {word} -->"


def knob_block(group: str) -> str:
    """A full generated README block, markers included."""
    return (f"{_marker(group, False)}\n"
            f"<!-- generated by `python -m daft_tpu.analysis --knob-docs "
            f"--write`; edit daft_tpu/analysis/knobs.py, not this table -->\n"
            f"{knob_table_markdown(group)}\n{_marker(group, True)}")


def readme_drift(readme_text: str) -> List[str]:
    """Human-readable drift problems between the registry and the README's
    generated knob-table blocks (empty list = in sync)."""
    problems = []
    for group in GROUPS:
        begin, end = _marker(group, False), _marker(group, True)
        i, j = readme_text.find(begin), readme_text.find(end)
        if i < 0 or j < 0:
            problems.append(
                f"README is missing the generated knob table for group "
                f"{group!r} (markers {begin} … {end})")
            continue
        current = readme_text[i:j + len(end)]
        if current != knob_block(group):
            problems.append(
                f"README knob table for group {group!r} is stale — "
                f"regenerate with `python -m daft_tpu.analysis --knob-docs "
                f"--write`")
    return problems


def update_readme(readme_path: str, write: bool = True) -> bool:
    """Rewrite every generated knob-table block in the README from the
    registry. Returns True when the file changed (or would change)."""
    with open(readme_path) as f:
        text = f.read()
    new = text
    for group in GROUPS:
        begin, end = _marker(group, False), _marker(group, True)
        i, j = new.find(begin), new.find(end)
        if i < 0 or j < 0:
            continue
        new = new[:i] + knob_block(group) + new[j + len(end):]
    changed = new != text
    if changed and write:
        with open(readme_path, "w") as f:
            f.write(new)
    return changed
