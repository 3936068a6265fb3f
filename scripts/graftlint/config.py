"""graftlint configuration: the contracts, spelled out in one place.

Everything here IS the contract surface — the passes are generic AST
machinery; which locks are hot, which loops are single-threaded dispatch
loops, which call names are store writes, which metric families must be
SIGUSR2-dumpable all live here so review of a contract change is a
one-file diff.

This module is import-light on purpose (stdlib only): it is imported by
the lint runner, by tests, and by scripts/check_slow_markers.py (the
chaos-suite file list lives here so suite enumeration has one home).
"""

# -- tree scope --------------------------------------------------------------

# package dirs scanned by every pass (repo-relative)
PACKAGES = ("kubernetes_tpu",)

# dirs skipped even inside PACKAGES
EXCLUDE_DIRS = ()

# -- chaos suites (shared with scripts/check_slow_markers.py and the
#    lock-order watchdog wiring) ---------------------------------------------

CHAOS_SUITE_FILES = [
    "tests/test_chaos_warmup.py",  # MUST run first: absorbs compiles
    "tests/test_chaos.py",
    "tests/test_chaos_pipeline.py",
    "tests/test_chaos_device.py",
    "tests/test_chaos_autoscaler.py",
    "tests/test_chaos_readpath.py",
    "tests/test_watchcache.py",
    "tests/test_chaos_ha.py",
    "tests/test_chaos_net.py",
    "tests/test_serving.py",
    "tests/test_chaos_serving.py",
    "tests/test_chaos_preempt.py",
    "tests/test_chaos_tuner.py",
    "tests/test_chaos_disk.py",
    "tests/test_chaos_defrag.py",
    "tests/test_chaos_relay.py",
]

# -- pass 1: donation safety -------------------------------------------------

# a donation site must sit lexically inside `with <...>.<suffix>(...):`
# for one of these generation-lease context managers (dotted suffix match
# on the called attribute: "donation_lease" matches
# `self.cache.encoder.donation_lease(donating=False)`) — the lease seals
# the live snapshot generation and hands the donor copy-on-pin buffers
# while readers pin an older generation
GENERATION_LEASE_SUFFIXES = ("donation_lease",)

# split-phase fast-path readback discipline (PR 17): methods that start
# an async device->host transfer of kernel outputs. The transfer reads
# buffers owned by the live snapshot generation, so the call must sit
# lexically inside a with-region that ties it to the generation
# lifecycle — the donation lease that launched the kernel (wave path)
# or an explicit generation pin (serial path). A fast-path readback
# escaping both races generation retirement: the donor may consume the
# buffers mid-transfer and the "fast" payload silently reads garbage.
FAST_READBACK_METHODS = ("copy_to_host_async",)
FASTPATH_LEASE_SUFFIXES = ("donation_lease", "pin_generation")

# the RETIRED big lock: the process-wide device_lock serialized every
# donation-bearing device entry point against every reader and is gone
# from the tree — any `with <...>.device_lock` anywhere is a finding
# (the wave path must never grow it back)
RETIRED_LOCK_SUFFIXES = ("device_lock",)

# keywords that make a jax.jit/shard_map expression donation-bearing
DONATION_KEYWORDS = ("donate_argnums", "donate_argnames")

# -- pass 2: dispatch-thread blocking calls ----------------------------------

# registered single-threaded dispatch loops, by qualified name
# ("Class.method"). Blocking primitives reachable from these (same-module
# call graph) are findings: one wedged call here stalls every client of
# the loop, not one request.
DISPATCH_ROOTS = (
    # watch-cache: the ONE store watch per kind + its fan-out
    "KindCache._run",
    "KindCache._apply",
    "KindCache._fanout",
    "Cacher._bookmark_loop",
    # store write path: every CRUD notify runs through this
    "APIServer._notify",
    # replication: ship() runs on the store write path; the heartbeat
    # loop services every follower from one thread
    "ReplicationListener.ship",
    "ReplicationListener._heartbeat_loop",
    # informer pump: one thread per informer, but a blocked pump freezes
    # every handler behind it
    "SharedInformer._run",
    # controller event pump: one thread drains all watch streams
    "WorkqueueController._watch_loop",
    # base watch primitives: push runs on the store/cacher dispatch
    # thread, stop on arbitrary callers including dispatch threads
    "Watcher.push",
    "Watcher.stop",
    # watch relay (kubernetes_tpu/relay/): the publisher pump feeds the
    # shared-memory ring from the cache fan-out, and each worker's
    # dispatch loop fans ring frames out to every connected client —
    # one blocking call in either stalls the whole kind (publisher) or
    # every client of the worker (dispatch). Intake/state-sync threads
    # are per-connection and MAY block; they are not reachable from
    # these roots.
    "RelayPublisher._pump",
    "RelayWorker._dispatch",
)

# extra reachability edges the same-module call graph can't see
# (root qualname -> called qualnames)
EXTRA_REACHABLE = {
    "APIServer._notify": ("Watcher.push", "Watcher.stop"),
    "KindCache._fanout": ("CacheWatcher.push_nonblock",),
}

# locks whose `with` bodies must stay free of blocking primitives and
# store RPCs (dotted suffix match). _gen_lock guards the snapshot
# generation pin/seal/install protocol (every lease operation crosses
# it); cache.lock serializes the whole scheduling pipeline.
HOT_LOCK_SUFFIXES = ("_gen_lock", "cache.lock")

# receiver names that make `.list(` / `.watch(` a store RPC
STORE_RPC_RECEIVERS = {"store", "_store", "server", "_server", "api", "client", "_client"}
STORE_RPC_METHODS = {"list", "watch"}

# -- pass 3: metrics contract ------------------------------------------------

# the human-facing metrics reference every series must appear in
METRICS_DOC = "README.md"

# series-name families that must be covered by a SIGUSR2 dump section
# (a snapshot_gauges/snapshot_counters call whose prefix covers the
# series). Families not listed are /metrics-only by design (e.g. the
# reference-aligned scheduler latency histograms).
DUMP_REQUIRED_FAMILIES = (
    "snapshot_",
    "kernel_guard_",
    "tracing_",
    "scheduler_device_",
    "scheduler_mesh_",
    "scheduler_wave_",
    "scheduler_pending_binds",
    "scheduler_bind_breaker",
    "node_lifecycle_",
    "autoscaler_",
    # heterogeneity/cost shape economics (subset of autoscaler_, listed
    # explicitly: the cheapest-feasible-shape acceptance metric must stay
    # dumpable even if the broad family ever narrows)
    "autoscaler_shape_cost_",
    # the vectorized priority/preemption engine + the legacy preemption
    # counters it extends
    "scheduler_preemption_",
    "preemption_",
    "watch_cache_",
    "apiserver_flowcontrol_",
    "informer_",
    "scheduler_ha_",
    "leader_election_",
    "restclient_",
    "follower_read_",
    "tuner_",
    # the durability surface: WAL sink fail-stop / corruption / fsync
    # stall state and the store's disk read-only + free-space gauges — a
    # store that went read-only for disk reasons must be SIGUSR2-visible
    "wal_",
    "store_disk_",
    # verified consolidation: the descheduler's plan/abort/wave counters
    # and the process-wide eviction token bucket it shares with
    # nodelifecycle, autoscaler scale-down, and preemption
    "descheduler_",
    "eviction_budget_",
    # the watch-relay tier: ring head/floor, publish/eviction/resync
    # counters, and worker fleet state must be SIGUSR2-visible — relay
    # workers are separate processes, so these publisher-side series are
    # the frontend's only in-process view of the tier
    "relay_",
)

# -- pass 4: degraded-write handling -----------------------------------------

# dirs whose store-write call sites must handle DegradedWrites/QuorumLost
DEGRADED_DIRS = (
    "kubernetes_tpu/controller",
    "kubernetes_tpu/scheduler",
    "kubernetes_tpu/autoscaler",
    "kubernetes_tpu/kubelet",
    "kubernetes_tpu/tuner",
    "kubernetes_tpu/descheduler",
)

# method names that are store writes when called on a store-ish receiver
WRITE_METHODS = {
    "create",
    "update",
    "guaranteed_update",
    "delete",
    "bind_pod",
    "bind_pods",
    "evict_pod",
    "write_events_bulk",
}

# receiver trailing names that identify the store / API client
WRITE_RECEIVERS = {
    "server",
    "_server",
    "store",
    "_store",
    "client",
    "_client",
    "api",
    "apiserver",
    "kube_client",
}

# exception names whose handlers count as degraded-write handling
# (DegradedWrites is a RuntimeError; QuorumLost subclasses DegradedWrites)
DEGRADED_HANDLERS = {
    "DegradedWrites",
    "QuorumLost",
    "RuntimeError",
    "Exception",
    "BaseException",
}

# classes whose every entry point already runs under a guarded reconcile
# loop (a worker that catches Exception and requeues rate-limited — the
# park-and-retry discipline). Subclasses inherit the exemption
# transitively. ReplicaSetController predates WorkqueueController but
# runs the identical guarded _worker shape.
DEGRADED_TOLERANT_BASES = {"WorkqueueController", "ReplicaSetController"}

# -- pass 6: guarded-by inference --------------------------------------------

# concurrency-critical classes whose `self._x` state the guarded-by pass
# indexes. For each attribute the pass infers the guarding lock from
# majority usage (accesses lexically inside `with <lock>` bodies or in
# functions whose every call site holds the lock, resolved through the
# call graph) and flags minority unguarded accesses. These are exactly
# the classes the post-device_lock concurrency model shares across
# threads: the encoder's generation table, the scheduler cache, the
# watch cache, the store, the scheduling queue, the ride-through buffer,
# and the elector.
GUARDEDBY_CLASSES = (
    "SnapshotEncoder",
    "SchedulerCache",
    "KindCache",
    "Cacher",
    "APIServer",
    "PriorityQueue",
    "BindRideThrough",
    "LeaderElector",
    "Tracer",
    "PhaseTracker",
    "WaveRingBuffer",
    "PolicyTuner",
)

# canonicalization of lock spellings to the runtime watchdog names
# (testing/lockgraph.py named_lock names), so the static pass, the
# dynamic lockset sanitizer, and `# graftlint: holds-<lock>` pragmas all
# speak one vocabulary. Keys are tried most-specific first:
# "<Class>.<attr>" for `with self.<attr>` inside <Class>, then the
# trailing "<recv>.<attr>" pair, then the bare attribute name.
GUARD_LOCK_ALIASES = {
    "SchedulerCache.lock": "scheduler.cache",
    "cache.lock": "scheduler.cache",
    "SnapshotEncoder._gen_lock": "encoder.gen_lock",
    "_gen_lock": "encoder.gen_lock",
    "KindCache._lock": "cacher.kind",
    "Cacher._lock": "cacher.top",
    "APIServer._lock": "store",
    "PriorityQueue._lock": "scheduler.queue",
    "PriorityQueue._cond": "scheduler.queue",
    "BindRideThrough._lock": "scheduler.ridethrough",
    # the anti-entropy auditor is handed the scheduler cache lock at
    # construction: its `with self.lock` IS the cache lock
    "SnapshotAntiEntropy.lock": "scheduler.cache",
    "Tracer._lock": "tracing.ring",
    "PhaseTracker._lock": "tracing.phase",
    "WaveRingBuffer._lock": "tuner.ring",
    "PolicyTuner._lock": "tuner.state",
}

# the human-facing attr→lock reference the inferred guard map must
# appear in (the `--list-guards` generator regenerates its table)
GUARDS_DOC = "README.md"

# -- stale-pragma audit -------------------------------------------------------

# suppression directives that MUST be consulted by some pass on their
# line: one of these surviving where no pass looks anymore is itself a
# finding (the pragma equivalent of a stale baseline entry). "holds-"
# prefixed directives are audited as a family.
AUDITED_PRAGMAS = (
    "allow-blocking",
    "degraded-ok",
    "fence-exempt",
    "walseam-exempt",
    "alias-safe",
    "unguarded",
    "guarded-by",
    "thread-ok",
    "span-ok",
)
AUDITED_PRAGMA_PREFIXES = ("holds-",)

# -- pass 5: scheduler bind-fence seam ---------------------------------------

# dirs whose bind-write call sites must funnel through the fence seam
# (scheduler-side only: that is where a leadership fence exists to attach)
FENCE_SEAM_DIRS = ("kubernetes_tpu/scheduler",)

# the ONE function allowed to call bind writes on a store receiver — it
# attaches the leadership fencing token the store/REST route validates
FENCE_SEAM_FUNCS = ("_bind_pods_fenced",)

# method names that are bind writes when called on a store-ish receiver
# (WRITE_RECEIVERS above)
FENCE_BIND_METHODS = {"bind_pod", "bind_pods"}

# -- pass 8: WAL-append fail-stop seam ----------------------------------------

# method names that are WAL appends when called on a WAL receiver. The
# durability contract is fail-stop (runtime/wal.py): these raise
# SinkFailed/DiskFull (OSError subclasses) and the CALL SITE must decide
# what the un-durable in-memory state means there — see walseam.py.
WAL_APPEND_METHODS = {"append", "append_batch", "append_commit"}

# receiver trailing names that identify a WriteAheadLog handle (dotted
# or bare — a local named `wal` is a WAL; there is no ambiguity to guard
# against the way bare store receivers need the parameter rule)
WAL_RECEIVERS = {"wal", "_wal"}

# functions (qualified names) that ARE the fail-stop seam: the one place
# a raw append is allowed without a lexical OSError handler, because the
# function's whole job is classifying the failure (un-ack the client,
# flip the write gate, classify DiskPressure vs DiskFailed)
WAL_FAILSTOP_SEAMS = ("APIServer._log_batch",)

# -- pass 7: tracing span lifecycle -------------------------------------------

# methods that OPEN a span (context managers): a call must be the
# context expression of a `with` item so the span closes on all exits
# (add_span/add_spans/add_span_many record closed intervals — exempt)
TRACING_SPAN_METHODS = ("span",)

# receiver trailing names identifying the tracer (utils/tracing.py)
TRACING_RECEIVERS = {"tracer", "tracing"}
