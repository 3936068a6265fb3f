"""ComponentConfig: versioned scheduler configuration.

KubeSchedulerConfiguration equivalent (reference
pkg/scheduler/apis/config/types.go:46,111,178): leader election, profiles,
DisablePreemption, PercentageOfNodesToScore (0 ⇒ adaptive),
Pod{Initial,Max}BackoffSeconds — plus the TPU-native knobs (device batch
size/window, encoding capacities)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..client.leaderelection import LeaderElectionConfig
from ..ops.encoding import EncodingConfig
from .extender import ExtenderConfig


@dataclass
class ProfileConfig:
    scheduler_name: str = "default-scheduler"
    # plugin overrides: None = algorithm-provider defaults (a PluginSet)
    plugin_set: Optional[object] = None
    score_weights: Optional[Dict[str, float]] = None


@dataclass
class KubeSchedulerConfiguration:
    leader_election: Optional[LeaderElectionConfig] = None
    disable_preemption: bool = False
    percentage_of_nodes_to_score: int = 0  # 0 => adaptive 50 - n/125
    pod_initial_backoff_seconds: float = 1.0
    pod_max_backoff_seconds: float = 10.0
    profiles: List[ProfileConfig] = field(
        default_factory=lambda: [ProfileConfig()]
    )
    extenders: List["ExtenderConfig"] = field(default_factory=list)
    hard_pod_affinity_weight: float = 1.0
    # RequestedToCapacityRatio piecewise shape ((utilization%, 0..10), ...);
    # None = the default {0%:0, 100%:10}. Threaded into BOTH the host
    # plugin and the device kernels (static per profile — a distinct shape
    # is a distinct kernel variant), so non-default profiles stay
    # device/host-consistent (requested_to_capacity_ratio.go:33)
    rtc_shape: Optional[List[Tuple[float, float]]] = None
    coscheduling_permit_timeout: float = 30.0  # gang quorum wait (Permit)
    # --- TPU-native section -------------------------------------------------
    use_device: bool = True  # TPUBatchScore profile gate
    use_mesh: bool = True  # shard the snapshot over all visible devices
    # (node-axis pjit; single-device processes run the unsharded kernel)
    # 0 = auto: 4096 on TPU backends (the kernel is template-shaped — the
    # pod axis appears only in small per-pod vectors, so a 4x batch costs
    # ~nothing on device and divides the fixed per-cycle sync cost by 4),
    # 1024 on CPU where kernel compute DOES scale with the batch
    device_batch_size: int = 0
    device_batch_window: float = 0.01  # linger to let bursts accumulate
    # (fuller batches amortize the fixed per-cycle cost); the former is
    # adaptive — it ships early once arrivals go idle (~3 ms), so this is a
    # burst cap, not a per-pod latency floor
    # batches at or below this size take the HOST path (the reference-shaped
    # per-pod scheduleOne) when the cluster is small enough that the Python
    # chain beats a device cycle (kernel + >=1 readback RTT). This is part
    # of the low-load p99 story (r4 verdict #4): the 450 ms kernel must not
    # serve a 1-pod batch. At larger clusters the host chain is SLOWER than
    # the kernel, so the gate is two-sided; big clusters use the small-pad
    # kernel variant with a narrow candidate list instead. 0 disables.
    small_batch_host_max: int = 4
    small_batch_host_node_max: int = 256
    # m_cand for the small padded-batch bucket (<=256 pods): a narrow
    # candidate list cuts the per-wave [P, M]-scaling cost ~4x for the
    # latency-sensitive tiny batches; 32 candidates per pod is ample when
    # the whole batch is 256 pods (the big bucket keeps wave_m_cand)
    wave_m_cand_small: int = 32
    # wave-pipeline depth: up to depth-1 launched batches stay in flight and
    # resolve in ONE combined device->host readback (the donated snapshot
    # chains batches on-device, so the sync is paid once per depth-1
    # batches instead of once per batch). 1 = fully synchronous, 2 = the old
    # depth-1 pipeline. Sustained-load readbacks/batch = 1/(depth-1).
    # 0 = auto = 2: on a local device or the CPU a deeper pipeline only
    # adds latency and host/device CPU contention.
    pipeline_depth: int = 0
    # split-phase readback: the kernel's chosen/placed/deferred index
    # payload (a few KB) streams back through an async device->host copy
    # started AT DISPATCH, so the bind-critical resolve never joins with
    # the bulk score tensor — that trails in a second transfer the guards
    # consume off the critical path (a late disagreement quarantines +
    # unwinds through the suspect-row machinery). This bounds the
    # trailing bulk readbacks awaiting validation: past this the
    # oldest is force-drained (one blocking readback) rather than letting
    # unvalidated payloads — and their generation pins — pile up behind a
    # slow transfer
    trailing_readback_max: int = 8
    encoding: EncodingConfig = field(default_factory=EncodingConfig)
    bind_workers: int = 16
    assume_ttl_seconds: float = 30.0
    # wave kernel (ops/wavelattice.py): vectorized bulk pass + W commit waves
    use_wave: bool = True  # False => serial scan lattice (oracle-exact)
    # route the wave kernel's resource-fit mask (fits0 + per-wave fits_w)
    # through the fused Pallas kernel (ops/pallas_ops.py) instead of the
    # XLA broadcast. None = auto: ON for TPU (measured on v5e, r5: 3185
    # vs 1696 pods/s on SchedulingPodAffinity/5000 — the fused mask avoids
    # materializing the [TPL, N, R] broadcast in HBM), OFF on CPU where
    # pallas runs interpreted. Explicit True/False overrides.
    use_pallas_fit: Optional[bool] = None
    # per-wave resource-score refresh at candidate nodes: later waves see
    # in-batch commits in their packing decisions (serial fidelity) for
    # O(P·M) gathers per wave. None = auto: ON for TPU backends (the cost
    # is noise next to the [TPL, N] stages there) and OFF on CPU, where
    # the same gathers are ~25% of kernel wall (measured: 898 -> 665
    # pods/s on the CPU A/B with it forced on). Explicit True/False
    # overrides; False is the round-3 behavior. Pinned by
    # test_wave_score_refresh_sees_in_batch_commits either way.
    wave_score_refresh: Optional[bool] = None
    # debug: cross-check every device placement against the HOST filter
    # chain per cycle (SURVEY §5's per-cycle verify mode — the live
    # analogue of the offline differential fuzz). Costs a host snapshot +
    # plugin run per placement; off outside debugging
    verify_cycles: bool = False
    # top-M candidate nodes per template. 0 = auto: 256 on CPU (r5 sweep,
    # per-wave cost scales with M x P: PodAffinity 978 -> 1513-1558
    # pods/s at 5k nodes, AntiAffinity +41%, Spreading +56%, everything
    # still fully scheduled — pods that miss the narrow list defer and
    # retry in the next batch's fresh waves); 512 on TPU, where the auto
    # batch is 4096 and a zone-concentrated single-template burst needs
    # enough distinct targets per batch (the hardware wavesweep arm
    # settles it). Explicit values override.
    wave_m_cand: int = 0
    # conflict-resolution waves for batches with hard (anti-affinity/
    # spread) pairs; static trip count — every such batch pays all waves
    # (one compiled variant per batch shape; ops/wavelattice.py). Batches
    # whose PRESENT templates carry no hard pairs use min(2,
    # wave_n_waves) (scheduler._batch_waves). On the chip the 16
    # iterations cost 7.3-7.4 ms a launch at the 256 bucket (3.3 ms for
    # 2; my chip runs, PR 34 and PR 35, perf5k-topologyspread.backlog;
    # PERF.md section 6); a batch with a hard pair takes at most four
    # pods an iteration from the queue (Scheduler._batch_limit).
    wave_n_waves: int = 16
    # degraded-store ride-through (scheduler/ridethrough.py): placements
    # whose bind 503s retryably park here (pods stay assumed, HBM snapshot
    # stays warm) while the breaker pauses dispatch; beyond capacity the
    # overflow unwinds through backoff like a failed bind
    pending_bind_capacity: int = 8192
    # --- data-plane self-defense (scheduler/antientropy.py, guards) ---------
    # validate every read-back batch before assume: chosen rows in range,
    # scores finite, plus the sampled host-oracle feasibility re-check
    # below; a violation quarantines the batch to the host fallback path
    # and forces a device snapshot rebuild (wrong placements become
    # structurally impossible — at worst a wave runs at host speed)
    kernel_output_guards: bool = True
    # pods per committed wave re-checked against the host filter chain's
    # pre-batch-sound subset (the online analogue of the differential
    # fuzz's oracle); 0 disables the sampled oracle (range/finite checks
    # stay on)
    guard_sample_per_wave: int = 4
    # snapshot anti-entropy: background auditor period (0 disables),
    # sampled rows per pass, and the consecutive-drifting-pass count that
    # escalates targeted re-scatter repair to a full snapshot rebuild
    antientropy_period_s: float = 5.0
    antientropy_sample_rows: int = 64
    antientropy_rebuild_after: int = 3
    # device-loss ride-through: bounded jittered retries for kernel
    # launches/readbacks that die with a device-loss error, and the
    # consecutive-loss count after which the device path is abandoned for
    # the host path (a chip that passes probes but fails every kernel
    # must not retry forever)
    device_retry_attempts: int = 2
    device_loss_disable_after: int = 3
    # --- priority & preemption (ops/preemptlattice.py) ----------------------
    # named score policy (ops/lattice.WEIGHT_PROFILES: "default", "pack",
    # "cheapest", "energy") or "" = derive weights from the profile's
    # score-plugin set. Policies are runtime weight VECTORS (a kernel
    # input), swappable live via Scheduler.set_score_policy.
    score_policy: str = ""
    # policy gym (tuner/): record real waves, replay candidate weight
    # vectors against them in a background loop, and promote winners
    # through a shadow A/B gate (persisted as the ScorePolicy API object
    # so failover adopts the tuned vector). Off by default — the tuner is
    # an opt-in control loop, not a scheduling dependency.
    tune_policy: bool = False
    # vectorized victim selection: one batched device pass ranks candidate
    # (node, victim-band) choices for a whole wave of unschedulable pods;
    # the host oracle (Preemptor._select_victims_on_node) still validates
    # the chosen node and selects the EXACT victim set before any
    # eviction. False = the per-pod host scan only (the pre-ISSUE-15 path)
    vector_preemption: bool = True
    # unschedulable pods per batch whose vector choice is ALSO checked
    # against the full host-path Preemptor scan (the sampled differential
    # oracle; a divergence beyond the documented tie-breaks counts in
    # scheduler_preemption_oracle_divergence_total and the oracle's answer
    # wins). 0 disables sampling (the per-node exact check stays on)
    preempt_verify_sample: int = 2

    def validate(self) -> None:
        if self.percentage_of_nodes_to_score < 0 or self.percentage_of_nodes_to_score > 100:
            raise ValueError("percentageOfNodesToScore must be in [0,100]")
        if self.pod_initial_backoff_seconds <= 0:
            raise ValueError("podInitialBackoffSeconds must be positive")
        if self.pod_max_backoff_seconds < self.pod_initial_backoff_seconds:
            raise ValueError("podMaxBackoffSeconds must be >= podInitialBackoffSeconds")
        if not self.profiles:
            raise ValueError("at least one profile required")
        names = [p.scheduler_name for p in self.profiles]
        if len(set(names)) != len(names):
            raise ValueError("duplicate profile schedulerName")
        if self.device_batch_size < 0:
            raise ValueError("device_batch_size must be >= 1, or 0 for auto")
        if self.pipeline_depth < 0:
            raise ValueError("pipeline_depth must be >= 1, or 0 for auto")
        if self.trailing_readback_max < 1:
            raise ValueError("trailing_readback_max must be >= 1")
        if self.pending_bind_capacity < 1:
            raise ValueError("pending_bind_capacity must be >= 1")
        if self.guard_sample_per_wave < 0:
            raise ValueError("guard_sample_per_wave must be >= 0")
        if self.antientropy_period_s < 0:
            raise ValueError("antientropy_period_s must be >= 0 (0 disables)")
        if self.antientropy_sample_rows < 1:
            raise ValueError("antientropy_sample_rows must be >= 1")
        if self.antientropy_rebuild_after < 1:
            raise ValueError("antientropy_rebuild_after must be >= 1")
        if self.device_retry_attempts < 0:
            raise ValueError("device_retry_attempts must be >= 0")
        if self.device_loss_disable_after < 1:
            raise ValueError("device_loss_disable_after must be >= 1")
        if self.preempt_verify_sample < 0:
            raise ValueError("preempt_verify_sample must be >= 0")
        if self.score_policy:
            from ..ops.lattice import weights_for_policy

            weights_for_policy(self.score_policy)  # raises on unknown names
        if self.leader_election is not None:
            self.leader_election.validate()
