# Build entry points (reference Makefile -> hack/make-rules/*):
#   make test             unit + integration suite (8-device CPU mesh)
#   make bench            headline benchmark (needs a TPU; fails without)
#   make chip-smoke       the served scheduling path on the chip, checked
#                         (chip_smoke.py; fails without a TPU)
#   make dryrun           multi-chip dryrun (virtual 8-device CPU mesh)
#   make verify           test + dryrun (the pre-commit gate)
#   make chaos            kill-primary + partition suites (slow soaks
#                         included) + the acked-write-loss checker selftest
#   make chaos-device     data-plane chaos only: snapshot corruption,
#                         poisoned kernel outputs, device-loss ride-through
#   make chaos-autoscaler autoscaler e2e only: scale-up bind budget, drain
#                         simulation gating, zero-eviction guarantee
#   make chaos-readpath   read-path chaos only: hollow-informer storms on
#                         the watch cache (one store watch per kind, zero
#                         relists after a flap, zero bind starvation)
#   make chaos-ha         scheduler-HA chaos only: kill the leader mid-wave
#                         (standby adopts, zero double-binds, fast first
#                         bind), zombie-leader bind fencing, graceful
#                         lease handoff, leader-election edge cases
#   make chaos-net        network/process chaos: REST control plane through
#                         the NetChaosProxy (blackholed bind acks, resets,
#                         partitions, half-open watches) + the multi-process
#                         leader/standby/zombie topology (SIGSTOP, fenced
#                         late REST binds, cross-process exactly-once ledger)
#   make chaos-serving    serving-tier chaos: multi-process frontend/follower
#                         fleet behind the balancer under mixed read/write
#                         storm with a frontend AND the read-serving follower
#                         SIGKILLed — zero acked-write loss, zero stale
#                         consistent reads, watchers resume with zero relists
#   make chaos-defrag     descheduler chaos: churn fragments the fleet, the
#                         verified consolidation loop provably reduces node
#                         count and $/h with zero acked-bind loss, zero PDB
#                         violations, zero gangs below min-member; forced
#                         mid-plan drift aborts + uncordon-rolls-back
#   make chaos-relay      watch-relay chaos: relay worker SIGKILLed mid-storm
#                         (clients resume at last rv, zero lost/dup ledger
#                         deliveries), ring overflow evicts slow clients
#                         without blocking dispatch, SIGSTOPped primary —
#                         relay keeps serving buffered frames + bookmarks
#   make chaos-tuner      policy-gym chaos: workload-mix flip re-convergence,
#                         kill-leader mid-shadow (no double promotion, the
#                         new leader adopts the persisted vector), NaN
#                         candidate rejected at the gate, degraded-store
#                         promotion pause
#   make tracing-ab       same-process tracing-overhead A/B (on vs off):
#                         acceptance rail — enabled-mode steady-state
#                         throughput regresses <3%, disabled ≈ noise
#   make lint-slow        fail if any chaos test >5s lacks the `slow` marker
#   make lint-static      graftlint: donation-safety, dispatch-blocking,
#                         metrics-contract, degraded-write, bind-fence,
#                         guarded-by inference + thread-hygiene +
#                         stale-pragma audit (scripts/graftlint/, empty
#                         suppression baseline); prints a per-pass
#                         findings/wall-time summary line
#   make lint-fast        graftlint --changed: full-tree analysis, findings
#                         scoped to files changed vs HEAD + their importers
#                         — the pre-commit loop (skips the slow-marker
#                         suite run); lint-static remains the merge gate
#   make lint             lint-static + lint-slow (invoked from `make chaos`)

PY ?= python

# Persistent JAX compilation cache for the chaos/lint targets: safe now
# that the generational snapshot keeps donation off reader-visible
# buffers (deserialized donating executables were the reason this was
# banned — see kubernetes_tpu/utils/compilation_cache.py). One cache dir
# across every pytest process kills the per-process compile storm.
JAX_CACHE ?= $(CURDIR)/.jax_cache
CACHED = JAX_COMPILATION_CACHE_DIR=$(JAX_CACHE)

.PHONY: test bench chip-smoke dryrun verify chaos \
	chaos-device chaos-autoscaler chaos-readpath chaos-ha chaos-net \
	chaos-serving chaos-preempt chaos-tuner chaos-disk chaos-defrag \
	chaos-relay tracing-ab lint-slow lint-static lint-fast lint

test:
	$(PY) -m pytest tests/ -q -m 'not slow'

chaos: lint
	$(CACHED) $(PY) -m pytest tests/test_chaos_warmup.py tests/test_consensus.py \
		tests/test_replication_quorum.py \
		tests/test_replication.py tests/test_chaos.py \
		tests/test_chaos_pipeline.py tests/test_chaos_device.py \
		tests/test_chaos_autoscaler.py tests/test_chaos_readpath.py \
		tests/test_watchcache.py tests/test_chaos_ha.py \
		tests/test_chaos_net.py tests/test_serving.py \
		tests/test_chaos_serving.py tests/test_chaos_preempt.py \
		tests/test_chaos_tuner.py tests/test_chaos_disk.py \
		tests/test_chaos_defrag.py tests/test_chaos_relay.py -q
	$(PY) scripts/consistency_check.py --selftest

chaos-device:
	$(CACHED) $(PY) -m pytest tests/test_chaos_warmup.py tests/test_chaos_device.py -q

chaos-autoscaler:
	$(CACHED) $(PY) -m pytest tests/test_chaos_warmup.py \
		tests/test_chaos_autoscaler.py -q

chaos-readpath:
	$(CACHED) $(PY) -m pytest tests/test_chaos_readpath.py tests/test_watchcache.py -q

chaos-ha:
	$(CACHED) $(PY) -m pytest tests/test_chaos_ha.py -q

chaos-net:
	$(CACHED) $(PY) -m pytest tests/test_chaos_net.py -q

chaos-serving:
	$(CACHED) $(PY) -m pytest tests/test_serving.py tests/test_chaos_serving.py -q

chaos-preempt:
	$(CACHED) $(PY) -m pytest tests/test_chaos_preempt.py -q

chaos-tuner:
	$(CACHED) $(PY) -m pytest tests/test_chaos_warmup.py tests/test_chaos_tuner.py -q

chaos-disk:
	$(CACHED) $(PY) -m pytest tests/test_chaos_disk.py -q
	$(PY) scripts/consistency_check.py --selftest

chaos-defrag:
	$(CACHED) $(PY) -m pytest tests/test_chaos_warmup.py \
		tests/test_chaos_defrag.py -q

chaos-relay:
	$(CACHED) $(PY) -m pytest tests/test_relay.py tests/test_chaos_relay.py -q

tracing-ab:
	JAX_PLATFORMS=cpu $(PY) scripts/tracing_overhead_ab.py

lint-slow:
	$(CACHED) $(PY) scripts/check_slow_markers.py

lint-static:
	$(PY) scripts/graftlint

lint-fast:
	$(PY) scripts/graftlint --changed

lint: lint-static lint-slow

bench:
	$(PY) bench.py

chip-smoke:
	$(PY) chip_smoke.py

dryrun:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

verify: test dryrun
