"""Obs-inventory lint: metric naming/help conformance + event schema.

Imports the process-wide registry (``easydarwin_tpu.obs``) and asserts
every registered family follows the convention documented in
ARCHITECTURE.md "Observability":

* names are snake_case (``[a-z][a-z0-9_]*``), no double underscores;
* counters end in ``_total``;
* histograms and gauges end in a unit suffix (``_seconds``, ``_bytes``,
  ``_ratio``, ``_total``, ``_count``);
* every family has non-empty help text that doesn't just restate the name;
* label names are snake_case and never the reserved ``le``;
* histogram bucket bounds are strictly increasing and finite.

It also lints the structured-event vocabulary (``obs.events.SCHEMA``):

* event names are dotted snake_case (``layer.action``);
* required field names are snake_case and never shadow the record
  envelope (``ts``/``level``/``event``/``session``/``stream``/``trace``);
* every ``emit("name", ...)`` call site in ``easydarwin_tpu/`` names a
  declared event — an undeclared emit would be flagged ``invalid`` at
  runtime, and this catches it at review time instead.

It also enforces the phase-attribution contract (``lint_phases``): the
``relay_phase_seconds`` label vocabulary is the CLOSED
``obs.profile.PHASES``/``ENGINES`` set, and the time histograms the
profiler/SLO layers read keep strictly-increasing bounds covering the
full TIME_BUCKETS range.

Run standalone (``python tools/metrics_lint.py``, exit 1 on violations)
or from the test suite (``tests/test_obs.py`` imports ``lint``,
``lint_events`` and ``lint_emit_sites``; ``tests/test_profile.py``
imports ``lint_phases``).
"""

from __future__ import annotations

import pathlib
import re
import sys

NAME_RE = re.compile(r"[a-z][a-z0-9_]*$")
#: ``_level`` is the degradation-ladder rung index (resilience/ladder.py)
#: — a dimensionless ordinal, the same way ``_count`` is; ``_info`` is
#: the Prometheus info-metric convention (a constant-1 gauge whose
#: labels carry the payload — egress_backend_info); ``_score`` is the
#: control plane's capacity figure (cluster_capacity_score — a
#: benchmark-derived rating in pps, quantized, not a raw measurement);
#: ``_live`` is the fleet federation's liveness-qualified node count
#: (fleet_nodes_live — a count qualified by state, like _count);
#: ``_subscribers`` is the audience observatory's population gauge
#: (audience_subscribers{tier,band} — a census count, like _live)
UNIT_SUFFIXES = ("_seconds", "_bytes", "_ratio", "_total", "_count",
                 "_level", "_info", "_score", "_live", "_subscribers")

EVENT_NAME_RE = re.compile(r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
#: emit("event.name", ...) — the positional literal, plain or f-string
#: (\s* spans newlines: a call wrapped after ``emit(`` still matches)
EMIT_SITE_RE = re.compile(r"""\bemit\(\s*(f?)['"]([^'"]+)['"]""")


def lint(registry) -> list[str]:
    """Return a list of human-readable violations (empty = clean)."""
    errs: list[str] = []
    for fam in registry.families():
        n = fam.name
        if not NAME_RE.match(n) or "__" in n:
            errs.append(f"{n}: not snake_case")
        if fam.kind == "counter" and not n.endswith("_total"):
            errs.append(f"{n}: counter must end in _total")
        if fam.kind in ("gauge", "histogram") \
                and not n.endswith(UNIT_SUFFIXES):
            errs.append(f"{n}: {fam.kind} must carry a unit suffix "
                        f"{UNIT_SUFFIXES}")
        if fam.kind == "histogram" and n.endswith("_total"):
            errs.append(f"{n}: histogram must not end in _total "
                        "(collides with counter convention)")
        if not (fam.help or "").strip():
            errs.append(f"{n}: missing help text")
        elif fam.help.strip().lower().replace(" ", "_") == n:
            errs.append(f"{n}: help text just restates the name")
        for ln in fam.label_names:
            if not NAME_RE.match(ln):
                errs.append(f"{n}: label {ln!r} not snake_case")
            if ln == "le":
                errs.append(f"{n}: label 'le' is reserved for histogram "
                            "buckets")
            if ln == "n":
                errs.append(f"{n}: label 'n' is reserved (the weighted-"
                            "observe parameter)")
        bounds = getattr(fam, "bounds", None)
        if bounds is not None:
            if any(b != b or b in (float("inf"), float("-inf"))
                   for b in bounds):
                errs.append(f"{n}: non-finite bucket bound")
            if list(bounds) != sorted(set(bounds)):
                errs.append(f"{n}: bucket bounds not strictly increasing")
    return errs


def lint_phases(registry, phases=None, engines=None) -> list[str]:
    """Phase-attribution contract (ISSUE 3): the ``relay_phase_seconds``
    family exists with the (engine, phase) label pair; every observed
    child stays inside the CLOSED ``obs.profile.PHASES`` / ``ENGINES``
    vocabulary (an open vocabulary would silently shard the histograms
    and break every dashboard ratio); the vocabulary itself is
    snake_case; and the time histograms the SLO/profiler layers read
    (``relay_phase_seconds``, ``relay_ingest_to_wire_seconds``) keep
    strictly-increasing bounds COVERING the shared TIME_BUCKETS range —
    a narrower ladder would clip ``count_above`` budgets and quantiles."""
    if phases is None or engines is None:
        from easydarwin_tpu.obs.profile import ENGINES, PHASES
        phases = phases or PHASES
        engines = engines or ENGINES
    from easydarwin_tpu.obs.metrics import TIME_BUCKETS
    errs: list[str] = []
    for v in tuple(phases) + tuple(engines):
        if not NAME_RE.match(v):
            errs.append(f"phase/engine vocabulary entry {v!r} not "
                        "snake_case")
    for fam_name in ("relay_phase_seconds", "relay_ingest_to_wire_seconds"):
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"{fam_name}: family missing from the registry")
            continue
        bounds = getattr(fam, "bounds", ())
        if list(bounds) != sorted(set(bounds)):
            errs.append(f"{fam_name}: bucket bounds not strictly "
                        "increasing")
        if not bounds or bounds[0] > TIME_BUCKETS[0] \
                or bounds[-1] < TIME_BUCKETS[-1]:
            errs.append(f"{fam_name}: bucket bounds do not cover the "
                        f"TIME_BUCKETS range [{TIME_BUCKETS[0]}, "
                        f"{TIME_BUCKETS[-1]}]")
    fam = None
    try:
        fam = registry.get("relay_phase_seconds")
    except KeyError:
        pass
    if fam is not None:
        if tuple(fam.label_names) != ("engine", "phase"):
            errs.append("relay_phase_seconds: labels must be "
                        "(engine, phase), got "
                        f"{tuple(fam.label_names)}")
        else:
            for engine, phase in getattr(fam, "_states", {}):
                if phase not in phases:
                    errs.append(f"relay_phase_seconds: observed phase "
                                f"{phase!r} outside the closed set "
                                f"{tuple(phases)}")
                if engine not in engines:
                    errs.append(f"relay_phase_seconds: observed engine "
                                f"{engine!r} outside the closed set "
                                f"{tuple(engines)}")
    return errs


#: megabatch mesh metrics: the device label is a SHARD INDEX, and a
#: serving mesh is bounded by one host's devices — anything past this is
#: an id string / hostname leaking into the label (unbounded cardinality)
MAX_MESH_SHARDS = 64
#: the per-device phase vocabulary (a subset of obs.profile.PHASES)
MESH_PHASES = ("h2d", "device_step", "d2h")


def lint_megabatch_devices(registry) -> list[str]:
    """The mesh-dispatch contract (ISSUE 7): the ``megabatch_device_*``
    families exist with their exact label sets; every observed
    ``device`` label is a decimal shard index below ``MAX_MESH_SHARDS``
    (never a backend device-id string — "TPU_v5litepod_4x4_..." would
    shard the family per hostname and break every per-device ratio);
    and the per-device phase vocabulary stays inside the closed
    ``MESH_PHASES`` subset of ``obs.profile.PHASES``.  The passes and
    streams families take every pass of a mesh scheduler — one that is
    not sharded (one row) once, on the device that ran
    it whole (ISSUE 37) — so the streams that did ride a sharded pass
    have a counter of their own, ``megabatch_sharded_streams_total``."""
    errs: list[str] = []
    want_labels = {
        "megabatch_device_passes_total": ("device",),
        "megabatch_device_streams_total": ("device",),
        "megabatch_device_phase_seconds": ("device", "phase"),
        "megabatch_sharded_streams_total": (),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"megabatch mesh family {fam_name} missing from "
                        "the registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")

    def check_device(fam_name: str, device: str) -> None:
        if not device.isdigit() or int(device) >= MAX_MESH_SHARDS:
            errs.append(f"{fam_name}: device label {device!r} is not a "
                        f"shard index < {MAX_MESH_SHARDS} (device-id "
                        "strings are unbounded-cardinality)")

    for fam_name in ("megabatch_device_passes_total",
                     "megabatch_device_streams_total"):
        for key in getattr(fams.get(fam_name), "_values", {}):
            check_device(fam_name, key[0])
    fam = fams.get("megabatch_device_phase_seconds")
    if fam is not None:
        from easydarwin_tpu.obs.profile import PHASES
        for device, phase in getattr(fam, "_states", {}):
            check_device("megabatch_device_phase_seconds", device)
            if phase not in MESH_PHASES:
                errs.append(f"megabatch_device_phase_seconds: phase "
                            f"{phase!r} outside the closed set "
                            f"{MESH_PHASES}")
            elif phase not in PHASES:
                errs.append(f"megabatch_device_phase_seconds: phase "
                            f"{phase!r} is in MESH_PHASES but missing "
                            "from obs.profile.PHASES (vocabularies out "
                            "of sync)")
    return errs


#: the closed effective-backend vocabulary (relay/fanout.py
#: EGRESS_BACKENDS minus "auto" — a REQUEST, never an effective rung);
#: an open set would shard egress_backend_info per typo and break the
#: forced-backend soak's equality assertion
EGRESS_BACKEND_LABELS = ("io_uring", "gso", "scalar")


def lint_egress_backends(registry, schema: dict) -> list[str]:
    """The egress-backend contract (ISSUE 8): the probe-ladder families
    exist with their exact label sets, every observed ``backend`` label
    stays inside the closed rung vocabulary, the
    ``egress.backend_fallback`` event is declared (soak --egress-backend
    and the fallback tests key on it), the backend-labelled egress phase
    is in the closed PHASES vocabulary, and the config-side ladder
    agrees with the lint's."""
    errs: list[str] = []
    want_labels = {
        "egress_backend_info": ("backend",),
        "egress_backend_fallbacks_total": ("backend",),
        "io_uring_sqe_total": (),
        "io_uring_cqe_total": (),
        "io_uring_submit_calls_total": (),
        "io_uring_zerocopy_completions_total": (),
        "io_uring_zerocopy_copied_total": (),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"egress backend family {fam_name} missing from "
                        "the registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    for fam_name in ("egress_backend_info",
                     "egress_backend_fallbacks_total"):
        for key in getattr(fams.get(fam_name), "_values", {}):
            if key and key[0] not in EGRESS_BACKEND_LABELS:
                errs.append(f"{fam_name}: observed backend {key[0]!r} "
                            f"outside the closed set "
                            f"{EGRESS_BACKEND_LABELS}")
    if "egress.backend_fallback" not in schema:
        errs.append("event egress.backend_fallback missing from SCHEMA")
    from easydarwin_tpu.obs.profile import PHASES
    if "egress_io_uring" not in PHASES:
        errs.append("phase 'egress_io_uring' missing from "
                    "obs.profile.PHASES")
    from easydarwin_tpu.relay.fanout import EGRESS_BACKENDS
    for b in EGRESS_BACKEND_LABELS:
        if b not in EGRESS_BACKENDS:
            errs.append(f"backend {b!r} missing from the config-side "
                        "EGRESS_BACKENDS ladder")
    if "auto" not in EGRESS_BACKENDS:
        errs.append("'auto' missing from the config-side EGRESS_BACKENDS "
                    "ladder")
    return errs


def lint_resilience(registry, schema: dict) -> list[str]:
    """The resilience contract (ISSUE 5): the fault-injection /
    degradation-ladder / checkpoint families exist with their exact
    label sets, the injection-site vocabulary is closed (an open set
    would shard ``fault_injected_total`` across typo'd sites), and the
    ``fault.*`` / ``ladder.*`` / ``ckpt.*`` event names are declared —
    the chaos soak and the flight recorder key on these names."""
    errs: list[str] = []
    want_labels = {
        "fault_injected_total": ("site",),
        "resilience_ladder_level": ("stream",),
        "resilience_transitions_total": ("direction",),
        "resilience_retries_total": (),
        "resilience_shed_outputs_total": (),
        "resilience_checkpoint_writes_total": (),
        "resilience_checkpoint_bytes_total": (),
        "resilience_checkpoint_restores_total": (),
        "resilience_checkpoint_errors_total": (),
    }
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"resilience family {fam_name} missing from the "
                        "registry")
            continue
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    from easydarwin_tpu.resilience.inject import SITES
    fam = None
    try:
        fam = registry.get("fault_injected_total")
    except KeyError:
        pass
    if fam is not None:
        for (site,) in getattr(fam, "_values", {}):
            if site not in SITES:
                errs.append(f"fault_injected_total: observed site "
                            f"{site!r} outside the closed set {SITES}")
    for name in ("fault.injected", "ladder.degrade", "ladder.recover",
                 "ladder.shed", "ckpt.save", "ckpt.restore"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    return errs


def lint_cluster(registry, schema: dict) -> list[str]:
    """The cluster-tier contract (ISSUE 6): the lease/placement/pull/
    migration families exist with their exact label sets, and the
    ``cluster.*`` / ``cms.device_offline`` event names are declared —
    ``tools/soak.py --cluster`` and the failover e2e key on them."""
    errs: list[str] = []
    want_labels = {
        "redis_errors_total": (),
        "cluster_lease_lost_total": (),
        "cluster_lease_fence_rejected_total": (),
        "cluster_placement_moves_total": (),
        "cluster_pull_retries_total": (),
        "cluster_pull_breaker_open_total": (),
        "cluster_migrations_total": (),
    }
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"cluster family {fam_name} missing from the "
                        "registry")
            continue
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    for name in ("cluster.lease_acquire", "cluster.lease_lost",
                 "cluster.fence_rejected", "cluster.placement_move",
                 "cluster.pull_retry", "cluster.breaker_open",
                 "cluster.breaker_close", "cluster.migrate",
                 "cluster.drain", "cms.device_offline"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    # the cluster fault sites ride the closed injection vocabulary
    from easydarwin_tpu.resilience.inject import SITES
    for site in ("lease_loss", "redis_partition", "pull_stall"):
        if site not in SITES:
            errs.append(f"cluster fault site {site!r} missing from the "
                        "closed SITES vocabulary")
    return errs


#: closed action vocabulary of ``cluster_admission_refused_total``
ADMISSION_ACTIONS = ("refuse", "redirect")


def lint_control_plane(registry, schema: dict) -> list[str]:
    """The load-aware control-plane contract (ISSUE 13): the capacity/
    utilization/rebalance/admission/relay-tree families exist with
    their exact label sets, every observed ``action`` label stays
    inside the closed refuse|redirect vocabulary, the
    ``cluster.rebalance`` / ``cluster.refuse`` event names are
    declared, and the control-plane fault sites ride the closed SITES
    vocabulary — ``tools/soak.py --skewed`` and the bench
    ``extra.rebalance`` section key on these."""
    errs: list[str] = []
    want_labels = {
        "cluster_capacity_score": (),
        "cluster_utilization_ratio": (),
        "cluster_rebalance_moves_total": (),
        "cluster_admission_refused_total": ("action",),
        "relay_tree_edges_total": (),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"control-plane family {fam_name} missing from "
                        "the registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    fam = fams.get("cluster_admission_refused_total")
    if fam is not None:
        for (action,) in getattr(fam, "_values", {}):
            if action not in ADMISSION_ACTIONS:
                errs.append(f"cluster_admission_refused_total: observed "
                            f"action {action!r} outside the closed set "
                            f"{ADMISSION_ACTIONS}")
    for name in ("cluster.rebalance", "cluster.refuse"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    from easydarwin_tpu.resilience.inject import SITES
    for site in ("capacity_spoof", "overload_spoof"):
        if site not in SITES:
            errs.append(f"control-plane fault site {site!r} missing "
                        "from the closed SITES vocabulary")
    return errs


def lint_requant(registry) -> list[str]:
    """The ABR-ladder requant contract (ISSUE 9): the pipeline families
    exist with their exact label sets, and every observed ``stage``
    label of ``requant_stage_seconds`` stays inside the CLOSED
    ``hls.requant.REQUANT_STAGES`` vocabulary (parse / entropy /
    transform_device / recode / reassemble) — an open vocabulary would
    shard the stage histogram and break the ladder dashboards;
    ``tools/soak.py --hls-ladder`` keys on these families."""
    errs: list[str] = []
    want_labels = {
        "requant_aus_total": (),
        "requant_renditions_total": (),
        "requant_shed_total": (),
        "requant_reassembly_mismatch_total": (),
        "requant_stage_seconds": ("stage",),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"requant family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    from easydarwin_tpu.hls.requant import REQUANT_STAGES
    for v in REQUANT_STAGES:
        if not NAME_RE.match(v):
            errs.append(f"requant stage vocabulary entry {v!r} not "
                        "snake_case")
    fam = fams.get("requant_stage_seconds")
    if fam is not None:
        for (stage,) in getattr(fam, "_states", {}):
            if stage not in REQUANT_STAGES:
                errs.append(f"requant_stage_seconds: observed stage "
                            f"{stage!r} outside the closed set "
                            f"{REQUANT_STAGES}")
    return errs


#: closed serving-path vocabulary of ``vod_packets_total``
VOD_PATHS = ("hot", "cold")


def lint_vod(registry) -> list[str]:
    """The VOD segment-cache contract (ISSUE 10): the cache/pacer
    families exist with their exact label sets, every observed ``path``
    label of ``vod_packets_total`` stays inside the closed hot|cold
    vocabulary, and the cache-fill phase / vod engine are declared in
    the closed profiler sets — ``tools/soak.py --vod`` and the bench
    ``extra.vod`` section key on these."""
    errs: list[str] = []
    want_labels = {
        "vod_cache_hits_total": (),
        "vod_cache_misses_total": (),
        "vod_cache_evictions_total": (),
        "vod_cache_bytes": (),
        "vod_sessions_count": (),
        "vod_packets_total": ("path",),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"vod family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    fam = fams.get("vod_packets_total")
    if fam is not None:
        for (path,) in getattr(fam, "_states", {}):
            if path not in VOD_PATHS:
                errs.append(f"vod_packets_total: observed path "
                            f"{path!r} outside the closed set "
                            f"{VOD_PATHS}")
    from easydarwin_tpu.obs.profile import ENGINES, PHASES
    if "cache_fill" not in PHASES:
        errs.append("phase 'cache_fill' missing from obs.profile.PHASES")
    if "vod" not in ENGINES:
        errs.append("engine 'vod' missing from obs.profile.ENGINES")
    return errs


#: closed parity-kind vocabulary of ``fec_parity_packets_total``
FEC_KINDS = ("xor", "rs")


def lint_fec(registry, schema: dict) -> list[str]:
    """The reliability-tier contract (ISSUE 11): the FEC/RTX families
    exist with their exact label sets, every observed ``kind`` label
    stays inside the closed xor|rs vocabulary, the receiver-side fault
    sites ride the closed SITES vocabulary, and the ``fec.*``/``rtx.*``
    event names are declared — ``tools/soak.py --lossy`` and the bench
    ``extra.fec`` section key on these."""
    errs: list[str] = []
    want_labels = {
        "fec_parity_packets_total": ("kind",),
        "fec_recovered_total": (),
        "fec_parity_oracle_mismatch_total": (),
        "fec_overhead_ratio": ("path", "track"),
        "rtx_sent_total": (),
        "rtx_giveup_total": (),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"fec family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    fam = fams.get("fec_parity_packets_total")
    if fam is not None:
        for (kind,) in getattr(fam, "_values", {}):
            if kind not in FEC_KINDS:
                errs.append(f"fec_parity_packets_total: observed kind "
                            f"{kind!r} outside the closed set "
                            f"{FEC_KINDS}")
    for name in ("fec.host_fallback", "rtx.giveup"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    from easydarwin_tpu.resilience.inject import SITES
    for site in ("egress_drop", "rr_loss_spoof"):
        if site not in SITES:
            errs.append(f"receiver-side fault site {site!r} missing "
                        "from the closed SITES vocabulary")
    return errs


def lint_dvr(registry) -> list[str]:
    """The DVR / time-shift contract (ISSUE 12): the spill/time-shift
    families exist with their exact (empty) label sets, the ``dvr.*`` /
    ``record.orphan`` event names are declared, and the ``spill`` phase
    / ``dvr`` engine are in the closed profiler sets —
    ``tools/soak.py --dvr`` and the bench ``extra.dvr`` section key on
    these."""
    errs: list[str] = []
    want_labels = {
        "dvr_windows_spilled_total": (),
        "dvr_spill_bytes": (),
        "dvr_timeshift_sessions_count": (),
        "dvr_catchup_joins_total": (),
        "dvr_retention_evictions_total": (),
    }
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"dvr family {fam_name} missing from the "
                        "registry")
            continue
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    from easydarwin_tpu.obs import events as ev
    for name in ("dvr.arm", "dvr.finalize", "dvr.catchup",
                 "record.orphan"):
        if name not in ev.SCHEMA:
            errs.append(f"event {name} missing from SCHEMA")
    from easydarwin_tpu.obs.profile import ENGINES, PHASES
    if "spill" not in PHASES:
        errs.append("phase 'spill' missing from obs.profile.PHASES")
    if "dvr" not in ENGINES:
        errs.append("engine 'dvr' missing from obs.profile.ENGINES")
    return errs


#: closed shard-kind vocabulary of ``storage_{shards,repairs}_total``
STORAGE_KINDS = ("data", "parity")
#: closed result vocabulary of ``storage_reconstructs_total``
STORAGE_RESULTS = ("ok", "failed")


def lint_storage(registry, schema: dict) -> list[str]:
    """The erasure-storage tier's contract (ISSUE 20): the storage_*
    families exist with their exact label sets, observed ``kind`` /
    ``result`` children stay inside the closed data|parity / ok|failed
    vocabularies, the ``fec_solve_singular_total`` caller-labeled
    counter exists (the gf_solve accounting satellite), and the
    ``storage.*`` event names are declared — the bench
    ``extra.storage`` section and the cluster soak's owner-kill
    assertions key on these."""
    errs: list[str] = []
    want_labels = {
        "storage_shards_total": ("kind",),
        "storage_reconstructs_total": ("result",),
        "storage_repairs_total": ("kind",),
        "storage_repair_bytes_total": (),
        "storage_scrub_errors_total": (),
        "fec_solve_singular_total": ("caller",),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"storage family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    closed = {"storage_shards_total": STORAGE_KINDS,
              "storage_repairs_total": STORAGE_KINDS,
              "storage_reconstructs_total": STORAGE_RESULTS}
    for fam_name, vocab in closed.items():
        fam = fams.get(fam_name)
        if fam is None:
            continue
        for (val,) in getattr(fam, "_values", {}):
            if val not in vocab:
                errs.append(f"{fam_name}: observed label {val!r} "
                            f"outside the closed set {vocab}")
    for name in ("storage.store", "storage.reconstruct",
                 "storage.repair", "storage.scrub_error",
                 "storage.solve_singular", "storage.host_fallback"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    return errs


#: closed backend/rung vocabulary for the stream-socket egress ladder
#: (ISSUE 14): io_uring → writev → buffered (the per-send asyncio rung)
STREAM_BACKENDS = ("io_uring", "writev", "buffered")


def lint_tcp_delivery(registry, schema: dict) -> list[str]:
    """The TCP/HTTP delivery contract (ISSUE 14): the stream-egress
    families exist with exactly a ``backend``/``rung`` label whose
    observed children stay inside the closed STREAM_BACKENDS set, the
    TCP checkpoint-parity counter exists, and the ``ckpt.tcp_*`` events
    are declared — ``tools/soak.py --mixed`` and the bench
    ``extra.tcp_delivery`` section key on these."""
    errs: list[str] = []
    want_labels = {
        "tcp_egress_packets_total": ("backend",),
        "tcp_egress_bytes_total": ("backend",),
        "tcp_egress_backpressure_sheds_total": ("backend",),
        "hls_segment_egress_bytes_total": ("rung",),
        "resilience_checkpoint_tcp_orphans_total": (),
    }
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"tcp-delivery family {fam_name} missing from "
                        "the registry")
            continue
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
            continue
        if labels:
            for key in getattr(fam, "_values", {}):
                if key and key[0] not in STREAM_BACKENDS:
                    errs.append(f"{fam_name}: {labels[0]}={key[0]!r} not "
                                f"in the closed set {STREAM_BACKENDS}")
    for name in ("ckpt.tcp_reattach", "ckpt.tcp_orphan"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    return errs


#: closed serving-tier vocabulary of ``fleet_streams_total`` (mirrors
#: obs.fleet.FLEET_TIERS — an open set would shard the federation gauge
#: per typo and break every cross-node dashboard sum)
FLEET_TIERS = ("live", "pull", "vod", "dvr", "hls")
#: freshness chains deeper than this are truncated by the stitcher; a
#: bigger hop label means the chain transport leaked garbage
MAX_FRESHNESS_HOPS = 16


def lint_fleet(registry, schema: dict) -> list[str]:
    """The fleet-observability contract (ISSUE 15): the federation /
    freshness / flight-dedupe families exist with their exact label
    sets, every observed ``tier`` label stays inside the closed
    FLEET_TIERS vocabulary, every observed ``hops`` label is a small
    decimal chain length, the ``fleet.*`` event names are declared,
    and the event envelope reserves the ``seq``/``node_id`` cursor and
    attribution keys — ``tools/soak.py --composed`` and the bench
    ``extra.composed`` section key on these."""
    errs: list[str] = []
    want_labels = {
        "fleet_nodes_live": (),
        "fleet_streams_total": ("tier",),
        "fleet_publishes_total": (),
        "relay_e2e_freshness_seconds": ("hops",),
        "flight_dumps_deduped_total": (),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"fleet family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    fam = fams.get("fleet_streams_total")
    if fam is not None:
        for (tier,) in getattr(fam, "_values", {}):
            if tier not in FLEET_TIERS:
                errs.append(f"fleet_streams_total: observed tier "
                            f"{tier!r} outside the closed set "
                            f"{FLEET_TIERS}")
    fam = fams.get("relay_e2e_freshness_seconds")
    if fam is not None:
        for (hops,) in getattr(fam, "_states", {}):
            if not hops.isdigit() or not 1 <= int(hops) \
                    <= MAX_FRESHNESS_HOPS:
                errs.append(f"relay_e2e_freshness_seconds: observed "
                            f"hops label {hops!r} is not a chain "
                            f"length in [1, {MAX_FRESHNESS_HOPS}]")
    for name in ("fleet.node_stale", "fleet.node_live"):
        if name not in schema:
            errs.append(f"event {name} missing from SCHEMA")
    from easydarwin_tpu.obs.events import RESERVED_KEYS
    for key in ("seq", "node_id"):
        if key not in RESERVED_KEYS:
            errs.append(f"event envelope key {key!r} missing from "
                        "RESERVED_KEYS (a free-form field could shadow "
                        "the cursor/attribution envelope)")
    try:
        from easydarwin_tpu.obs.fleet import FLEET_TIERS as SRC_TIERS
        if tuple(SRC_TIERS) != FLEET_TIERS:
            errs.append(f"obs.fleet.FLEET_TIERS {tuple(SRC_TIERS)} out "
                        f"of sync with the lint's {FLEET_TIERS}")
    except ImportError:
        errs.append("obs.fleet module missing")
    return errs


def lint_ledger(registry) -> list[str]:
    """The wake-ledger contract (ISSUE 16): the ``pump_*`` families
    exist with exactly a ``work_class`` label, every observed child
    stays inside the CLOSED ``obs.ledger.WORK_CLASSES`` vocabulary (an
    open set would shard the wait/service histograms and break every
    blame ratio), the ledger histograms ride the full shared
    TIME_BUCKETS ladder, and the ladder's top bucket exceeds the SLO
    watchdog's worst window — a wait that outlives the slow window must
    still resolve into a finite bucket, not the +Inf catch-all, or the
    blame report's p99 saturates exactly when it matters most."""
    errs: list[str] = []
    from easydarwin_tpu.obs.ledger import WORK_CLASSES
    from easydarwin_tpu.obs.metrics import TIME_BUCKETS
    from easydarwin_tpu.obs.slo import SloConfig
    for v in WORK_CLASSES:
        if not NAME_RE.match(v):
            errs.append(f"work-class vocabulary entry {v!r} not "
                        "snake_case")
    want_labels = {
        "pump_wait_seconds": ("work_class",),
        "pump_service_seconds": ("work_class",),
        "pump_deferred_total": ("work_class",),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"ledger family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
    for fam_name in ("pump_wait_seconds", "pump_service_seconds"):
        fam = fams.get(fam_name)
        if fam is None:
            continue
        bounds = getattr(fam, "bounds", ())
        if not bounds or bounds[0] > TIME_BUCKETS[0] \
                or bounds[-1] < TIME_BUCKETS[-1]:
            errs.append(f"{fam_name}: bucket bounds do not cover the "
                        f"TIME_BUCKETS range [{TIME_BUCKETS[0]}, "
                        f"{TIME_BUCKETS[-1]}]")
        for (wc,) in getattr(fam, "_states", {}):
            if wc not in WORK_CLASSES:
                errs.append(f"{fam_name}: observed work_class {wc!r} "
                            f"outside the closed set {WORK_CLASSES}")
    fam = fams.get("pump_deferred_total")
    if fam is not None:
        for (wc,) in getattr(fam, "_values", {}):
            if wc not in WORK_CLASSES:
                errs.append(f"pump_deferred_total: observed work_class "
                            f"{wc!r} outside the closed set "
                            f"{WORK_CLASSES}")
    # the multi-second regime (ISSUE 16 satellite 1): the ladder's top
    # finite bucket must exceed the watchdog's worst window
    cfg = SloConfig()
    worst = max(cfg.fast_window_s, cfg.slow_window_s)
    if TIME_BUCKETS[-1] <= worst:
        errs.append(f"TIME_BUCKETS top bucket {TIME_BUCKETS[-1]}s does "
                    f"not exceed the SLO watchdog's worst window "
                    f"{worst}s — ledger waits would saturate into +Inf")
    return errs


#: how a span gets its name at a call site: the tracer's open/close
#: form and its post-hoc ``add``, the engine's and the native module's
#: wrappers, and the ledger's units (``pump.<work_class>``)
SPAN_SITE_RE = re.compile(
    r"""\b(?:TRACER\.(?:open|add)|_open|_egress_open|unit_start)"""
    r"""\(\s*(?:(?:self\._lib|lib)\s*,\s*)?(f?)['"]([^'"]+)['"]""")
PUMP_STATES = ("wake", "sleep")
BUILD_PARTS = ("trace", "lower", "backend")
WAKE_CAUSES = ("ingest", "timer", "interval")
STEP_RESULTS = ("idle", "worked")
CELL_KINDS = ("real", "staged")
PAIR_KINDS = ("handed", "walked")
PIPELINE_PARTS = ("send", "hidden")


def lint_spans(registry, root: pathlib.Path | None = None) -> list[str]:
    """The span contract (ISSUE 25): ``obs.trace.SPANS`` is CLOSED —
    dotted lower-case names, one ``pump.<work_class>`` per ledger class,
    and every span name written at a call site under ``root`` (the
    package) is in it (an f-string site by its literal prefix, against
    ``SPAN_PREFIXES``) — and the families read off the same brackets
    exist with their closed label sets: ``pump_loop_seconds_total
    {state}``, ``pump_wakes_total{cause}``, ``pump_wake_seconds``,
    ``relay_due_to_wire_seconds{engine}`` on a ladder covering
    TIME_BUCKETS, ``engine_outputs_walked_total`` / ``_due_total``,
    ``engine_plan_rebuilds_total`` (the ring-only ``engine.plan`` span's
    counter: one per rebuilt output plan, ISSUE 27), and the camera
    wall's three (ISSUE 30): ``engine_steps_total{result}`` at
    ``engine.step``'s exits, ``megabatch_cells_total{kind}`` per
    dispatched pass, ``ingest_interleaved_packets_total`` /
    ``_seconds_total`` off ``ingest.read``; and the drain's two (ISSUE
    31), counted where ``pump.sleep`` closes: ``pump_drain_rounds_total``
    / ``pump_drain_packets_total``; and the ready set's three (ISSUE 33):
    ``pump_roster_streams_total`` / ``pump_stepped_streams_total`` once a
    wake, ``pump_ready_missed_total`` by the 1 Hz audit; and the kept
    roster's two (ISSUE 40): ``pump_routed_streams_total`` once a wake,
    ``pump_roster_stale_total`` by the same audit; and the
    scheduler's hand-over (ISSUE 36): ``megabatch_pairs_total{kind}``,
    handed in ``begin_wake`` and walked in ``end_wake``; and the send
    pipeline's two (ISSUE 38), counted once a wake that handed the
    native sender a job, from the jobs' own stamps and the
    ``egress.wait`` spans: ``egress_pipeline_seconds_total{part}``,
    ``egress_pipeline_jobs_total``; and what a process did before its
    window (ISSUE 39): a ``boot.<phase>`` span per ``obs.boot.PHASES``
    entry, set once into ``server_boot_seconds{phase}`` with their
    ``total`` and emitted as ``server.boot``; the three counters a
    ``jax.build`` span and event is counted by,
    ``jax_executable_build_seconds_total{phase}`` by its three parts;
    ``rtsp_request_seconds_total{method}`` / ``rtsp_requests_total
    {method}`` where ``rtsp.<method>`` is filed."""
    from easydarwin_tpu.obs.boot import PHASES as BOOT_PHASES
    from easydarwin_tpu.obs.events import SCHEMA
    from easydarwin_tpu.obs.ledger import WORK_CLASSES
    from easydarwin_tpu.obs.metrics import TIME_BUCKETS
    from easydarwin_tpu.obs.trace import SPAN_PREFIXES, SPANS
    errs: list[str] = []
    if len(set(SPANS)) != len(SPANS):
        errs.append("span vocabulary has duplicates")
    for name in SPANS:
        if not EVENT_NAME_RE.match(name):
            errs.append(f"span {name!r}: not dotted snake_case")
    for wc in WORK_CLASSES:
        if f"pump.{wc}" not in SPANS:
            errs.append(f"span pump.{wc} missing: every ledger work class "
                        "is a span")
    for ph in BOOT_PHASES:
        if f"boot.{ph}" not in SPANS:
            errs.append(f"span boot.{ph} missing: every boot phase is a "
                        "span")
    if tuple(SCHEMA.get("server.boot", ())) != BOOT_PHASES + ("total",):
        errs.append("event server.boot must carry the boot phases and "
                    "their total")
    if "jax.build" not in SCHEMA:
        errs.append("event jax.build missing from SCHEMA")
    for py in sorted(root.rglob("*.py")) if root else ():
        text = py.read_text(encoding="utf-8", errors="replace")
        for m in SPAN_SITE_RE.finditer(text):
            line_no = text.count("\n", 0, m.start()) + 1
            is_f, name = m.group(1), m.group(2)
            if "unit_start" in m.group(0):
                name = f"pump.{name}"
            if is_f:
                if not name.split("{")[0].startswith(SPAN_PREFIXES):
                    errs.append(f"{py.name}:{line_no}: f-string span "
                                f"{name!r} outside {SPAN_PREFIXES}")
            elif name not in SPANS:
                errs.append(f"{py.name}:{line_no}: span {name!r} outside "
                            "the closed vocabulary obs.trace.SPANS")
    want = {"pump_loop_seconds_total": (("state",), PUMP_STATES),
            "pump_wakes_total": (("cause",), WAKE_CAUSES),
            "pump_wake_seconds": ((), ()),
            "pump_drain_rounds_total": ((), ()),
            "pump_drain_packets_total": ((), ()),
            "pump_roster_streams_total": ((), ()),
            "pump_stepped_streams_total": ((), ()),
            "pump_ready_missed_total": ((), ()),
            "pump_routed_streams_total": ((), ()),
            "pump_roster_stale_total": ((), ()),
            "relay_due_to_wire_seconds": (("engine",), ()),
            "engine_outputs_walked_total": ((), ()),
            "engine_outputs_due_total": ((), ()),
            "engine_plan_rebuilds_total": ((), ()),
            "engine_steps_total": (("result",), STEP_RESULTS),
            "megabatch_cells_total": (("kind",), CELL_KINDS),
            "megabatch_pairs_total": (("kind",), PAIR_KINDS),
            "egress_pipeline_seconds_total": (("part",), PIPELINE_PARTS),
            "egress_pipeline_jobs_total": ((), ()),
            "ingest_interleaved_packets_total": ((), ()),
            "ingest_interleaved_seconds_total": ((), ()),
            "server_boot_seconds": (("phase",), BOOT_PHASES + ("total",)),
            "jax_executables_built_total": ((), ()),
            "jax_persistent_cache_hits_total": ((), ()),
            "jax_executable_build_seconds_total": (("phase",), BUILD_PARTS),
            "rtsp_request_seconds_total": (("method",), ()),
            "rtsp_requests_total": (("method",), ())}
    for fam_name, (labels, closed) in want.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"span-side family {fam_name} missing from the "
                        "registry")
            continue
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
        elif closed:
            for (v,) in getattr(fam, "_values", {}):
                if v not in closed:
                    errs.append(f"{fam_name}: observed {labels[0]} {v!r} "
                                f"outside the closed set {closed}")
        bounds = getattr(fam, "bounds", None)
        if bounds is not None and (bounds[0] > TIME_BUCKETS[0]
                                   or bounds[-1] < TIME_BUCKETS[-1]):
            errs.append(f"{fam_name}: bucket bounds do not cover the "
                        "TIME_BUCKETS range")
    return errs


def lint_audience(registry, schema: dict | None = None) -> list[str]:
    """The audience observatory's contract (ISSUE 18): the four
    ``audience_*`` families exist with exactly the declared labels,
    every observed ``tier`` stays inside the CLOSED vocabulary (which
    must itself stay in sync with ``obs.fleet.FLEET_TIERS`` — one axis
    for fleet and audience dashboards), every observed ``band`` stays
    inside the closed good/fair/poor set, the QoE histogram's bucket
    ladder is bounded [0, 1] (the score formula clips there — a bucket
    past 1 would hide a formula regression), no audience family uses a
    reserved label, and the stall-storm event is declared."""
    errs: list[str] = []
    from easydarwin_tpu.obs.audience import (
        AUDIENCE_TIERS, BANDS, QOE_BUCKETS)
    try:
        from easydarwin_tpu.obs.fleet import FLEET_TIERS
        if tuple(FLEET_TIERS) != tuple(AUDIENCE_TIERS):
            errs.append(f"obs.audience.AUDIENCE_TIERS "
                        f"{tuple(AUDIENCE_TIERS)} out of sync with "
                        f"obs.fleet.FLEET_TIERS {tuple(FLEET_TIERS)}")
    except ImportError:
        errs.append("obs.fleet module missing")
    for v in AUDIENCE_TIERS + BANDS:
        if not NAME_RE.match(v):
            errs.append(f"audience vocabulary entry {v!r} not "
                        "snake_case")
    want_labels = {
        "audience_qoe_score": ("tier",),
        "audience_stall_seconds_total": ("tier",),
        "audience_subscribers": ("tier", "band"),
        "audience_stall_storms_total": (),
    }
    fams = {}
    for fam_name, labels in want_labels.items():
        try:
            fam = registry.get(fam_name)
        except KeyError:
            errs.append(f"audience family {fam_name} missing from the "
                        "registry")
            continue
        fams[fam_name] = fam
        if tuple(fam.label_names) != labels:
            errs.append(f"{fam_name}: labels must be {labels}, got "
                        f"{tuple(fam.label_names)}")
        for ln in fam.label_names:
            if ln == "le":
                errs.append(f"{fam_name}: reserved label 'le'")
    qoe = fams.get("audience_qoe_score")
    if qoe is not None:
        bounds = getattr(qoe, "bounds", ())
        if tuple(bounds) != tuple(sorted(float(b) for b in QOE_BUCKETS)):
            errs.append("audience_qoe_score: bucket bounds out of sync "
                        "with obs.audience.QOE_BUCKETS")
        if bounds and (bounds[0] <= 0.0 or bounds[-1] != 1.0):
            errs.append(f"audience_qoe_score: bounds must span (0, 1] "
                        f"with a closing 1.0 bucket, got "
                        f"[{bounds[0]}, {bounds[-1]}] — the QoE score "
                        "is clipped to [0, 1] by construction")
        for key in getattr(qoe, "_states", {}):
            (tier,) = key
            if tier not in AUDIENCE_TIERS:
                errs.append(f"audience_qoe_score: observed tier "
                            f"{tier!r} outside the closed set "
                            f"{tuple(AUDIENCE_TIERS)}")
    fam = fams.get("audience_stall_seconds_total")
    if fam is not None:
        for (tier,) in getattr(fam, "_values", {}):
            if tier not in AUDIENCE_TIERS:
                errs.append(f"audience_stall_seconds_total: observed "
                            f"tier {tier!r} outside the closed set "
                            f"{tuple(AUDIENCE_TIERS)}")
    fam = fams.get("audience_subscribers")
    if fam is not None:
        for tier, band in getattr(fam, "_values", {}):
            if tier not in AUDIENCE_TIERS:
                errs.append(f"audience_subscribers: observed tier "
                            f"{tier!r} outside the closed set "
                            f"{tuple(AUDIENCE_TIERS)}")
            if band not in BANDS:
                errs.append(f"audience_subscribers: observed band "
                            f"{band!r} outside the closed set "
                            f"{tuple(BANDS)}")
    if schema is not None and "audience.stall_storm" not in schema:
        errs.append("event audience.stall_storm missing from SCHEMA")
    return errs


def lint_events(schema: dict, reserved=None) -> list[str]:
    """Validate the structured-event vocabulary table itself."""
    if reserved is None:
        from easydarwin_tpu.obs import events as ev
        reserved = ev.RESERVED_KEYS
    errs: list[str] = []
    for name, fields in schema.items():
        if not EVENT_NAME_RE.match(name):
            errs.append(f"event {name}: not dotted snake_case "
                        "(layer.action)")
        for f in fields:
            if not NAME_RE.match(f):
                errs.append(f"event {name}: field {f!r} not snake_case")
            if f in reserved:
                errs.append(f"event {name}: field {f!r} shadows the "
                            "record envelope")
    return errs


def lint_emit_sites(root: pathlib.Path, schema: dict) -> list[str]:
    """Every ``emit("...")`` literal in the source tree must name a
    declared event — the static counterpart of the runtime
    ``events_invalid_total`` flag.  Whole-file scan, so calls wrapped
    after ``emit(`` are covered; f-string sites (``emit(f"rtsp.{x}")``)
    are checked as prefix families against the declared names."""
    errs: list[str] = []
    for py in sorted(root.rglob("*.py")):
        text = py.read_text(encoding="utf-8", errors="replace")
        for m in EMIT_SITE_RE.finditer(text):
            line_no = text.count("\n", 0, m.start()) + 1
            is_f, name = m.group(1), m.group(2)
            if is_f:
                # dynamic name: require the literal prefix up to the
                # first placeholder to match at least one declared event
                prefix = name.split("{")[0]
                if not any(ev.startswith(prefix) for ev in schema):
                    errs.append(f"{py.name}:{line_no}: f-string emit "
                                f"prefix {prefix!r} matches no declared "
                                "event")
                continue
            if not EVENT_NAME_RE.match(name):
                continue                # not an event emit (no layer dot)
            if name not in schema:
                errs.append(f"{py.name}:{line_no}: emit of undeclared "
                            f"event {name!r}")
    return errs


def main() -> int:
    sys.path.insert(0, ".")
    from easydarwin_tpu import obs
    from easydarwin_tpu.obs import events as ev
    errs = lint(obs.REGISTRY)
    errs += lint_phases(obs.REGISTRY)
    errs += lint_events(ev.SCHEMA)
    pkg = pathlib.Path(__file__).resolve().parents[1] / "easydarwin_tpu"
    errs += lint_emit_sites(pkg, ev.SCHEMA)
    # the SLO watchdog's vocabulary must be declared, not just emitted
    # somewhere: the soak/test layers key on these exact names
    for name in ("slo.violation", "slo.recover"):
        if name not in ev.SCHEMA:
            errs.append(f"event {name} missing from SCHEMA")
    # the megabatch scheduler's vocabulary (ISSUE 4): the engine label,
    # its phases, and the counter families the soak/bench layers key on
    # — a vocabulary revert would silently orphan their checks
    from easydarwin_tpu.obs.profile import ENGINES, PHASES
    if "megabatch" not in ENGINES:
        errs.append("engine 'megabatch' missing from obs.profile.ENGINES")
    for ph in ("stage_gather", "h2d_overlap"):
        if ph not in PHASES:
            errs.append(f"phase {ph!r} missing from obs.profile.PHASES")
    for fam in ("megabatch_passes_total", "megabatch_streams_total",
                "megabatch_fallback_total", "megabatch_wire_mismatch_total",
                "stage_gather_bytes_total",
                "stage_gather_busy_seconds_total"):
        try:
            obs.REGISTRY.get(fam)
        except KeyError:
            errs.append(f"megabatch family {fam} missing from the registry")
    # the mesh-dispatch vocabulary (ISSUE 7): megabatch_device_* family
    # set, shard-index device labels, closed per-device phase subset
    errs += lint_megabatch_devices(obs.REGISTRY)
    # the resilience subsystem's vocabulary (ISSUE 5): fault sites,
    # ladder rung gauge, checkpoint counters and the fault.*/ladder.*/
    # ckpt.* event schema
    errs += lint_resilience(obs.REGISTRY, ev.SCHEMA)
    # the cluster tier's vocabulary (ISSUE 6): lease/placement/pull/
    # migration families + cluster.* events + cluster fault sites
    errs += lint_cluster(obs.REGISTRY, ev.SCHEMA)
    # the load-aware control plane's vocabulary (ISSUE 13): capacity/
    # utilization/rebalance/admission families + the closed admission
    # action set + cluster.rebalance/refuse events + spoof fault sites
    errs += lint_control_plane(obs.REGISTRY, ev.SCHEMA)
    # the egress-backend ladder's vocabulary (ISSUE 8): probe families,
    # closed backend labels, the fallback event, the io_uring phase
    errs += lint_egress_backends(obs.REGISTRY, ev.SCHEMA)
    # the ABR requant ladder's vocabulary (ISSUE 9): pipeline counter
    # families + the closed requant stage set
    errs += lint_requant(obs.REGISTRY)
    # the VOD segment cache's vocabulary (ISSUE 10): cache/pacer
    # families + the closed hot|cold path set + the cache_fill phase
    errs += lint_vod(obs.REGISTRY)
    # the reliability tier's vocabulary (ISSUE 11): FEC/RTX families +
    # the closed xor|rs kind set + receiver-side fault sites + events
    errs += lint_fec(obs.REGISTRY, ev.SCHEMA)
    # the DVR / time-shift tier's vocabulary (ISSUE 12): spill/session
    # families + dvr.* events + the spill phase / dvr engine
    errs += lint_dvr(obs.REGISTRY)
    # the erasure-storage tier's vocabulary (ISSUE 20): storage_*
    # families with closed data|parity / ok|failed sets, the gf_solve
    # singular accounting counter, and the storage.* events
    errs += lint_storage(obs.REGISTRY, ev.SCHEMA)
    # the TCP/HTTP delivery tier's vocabulary (ISSUE 14): stream-egress
    # families with the closed io_uring/writev/buffered rung set + the
    # checkpoint-parity counter and ckpt.tcp_* events
    errs += lint_tcp_delivery(obs.REGISTRY, ev.SCHEMA)
    # the fleet observability layer's vocabulary (ISSUE 15): federation
    # gauges with the closed tier set, the freshness chain histogram,
    # fleet.* events and the seq/node_id event envelope
    errs += lint_fleet(obs.REGISTRY, ev.SCHEMA)
    # the wake ledger's vocabulary (ISSUE 16): pump_* families with the
    # closed work_class set + the multi-second bucket ladder whose top
    # exceeds the SLO watchdog's worst window
    errs += lint_ledger(obs.REGISTRY)
    # the audience observatory's vocabulary (ISSUE 18): audience_*
    # families with closed tier/band sets (tier synced with the fleet
    # vocabulary), the [0, 1] QoE bucket ladder and the stall-storm
    # event declaration
    errs += lint_audience(obs.REGISTRY, ev.SCHEMA)
    # the span vocabulary (ISSUE 25): obs.trace.SPANS closed over every
    # call site in the package + the pump_loop / pump_wakes /
    # due_to_wire / engine_outputs families read off the same brackets
    errs += lint_spans(obs.REGISTRY, pkg)
    for e in errs:
        print(f"metrics_lint: {e}", file=sys.stderr)
    if not errs:
        print(f"metrics_lint: {len(obs.REGISTRY.families())} families, "
              f"{len(ev.SCHEMA)} events OK")
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
