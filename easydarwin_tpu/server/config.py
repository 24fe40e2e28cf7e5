"""Layered server configuration.

The reference layers CLI getopt → XML prefs (``easydarwin.xml``) → a typed
table of ~85 prefs with defaults (``QTSServerPrefs.cpp:190-280``) → SIGHUP /
REST-triggered ``RereadPrefs`` role rebroadcast.  Here: a typed dataclass
with the same key prefs, TOML load/save (stdlib ``tomllib``), and change
listeners that components subscribe to (the RereadPrefs equivalent).
"""

from __future__ import annotations

import dataclasses

try:
    import tomllib
except ModuleNotFoundError:        # Python < 3.11: same API from tomli
    import tomli as tomllib        # type: ignore[no-redef]
from dataclasses import dataclass, field
from typing import Callable


@dataclass
class ServerConfig:
    # --- core ports (QTSServerPrefs: rtsp_port 222, service ports 273-274)
    rtsp_port: int = 10554
    service_port: int = 10008          # REST API (service_lan_port)
    bind_ip: str = "0.0.0.0"
    # --- relay tuning (ReflectorStream.cpp:56-68 + prefs)
    bucket_size: int = 16
    bucket_delay_ms: int = 73
    overbuffer_sec: float = 10.0
    max_packet_age_sec: float = 20.0
    ring_capacity: int = 4096
    reflect_interval_ms: int = 20      # sender wake cadence (ref: 200 ms)
    # --- session management
    rtsp_timeout_sec: int = 120        # idle RTSP session kill
    push_timeout_sec: int = 20         # broadcaster refresh window
    timeout_sweep_sec: int = 15        # TimeoutTask.h:66 granularity
    # --- VOD
    movie_folder: str = "/tmp/movies"
    # --- VOD segment cache (ISSUE 10: vod/cache.py + the group pacer).
    # On: PLAY on a file path is served by the shared group pacer — hot
    # assets' samples are pre-packed into the fixed-slot ring-window
    # format once and every subscriber rides the same megabatch/affine
    # engine as live relay; cache misses stream through the cold mmap
    # path while a background fill packs the window.  Off: every player
    # gets the per-session asyncio FileSession (the pre-ISSUE-10 path,
    # still used for Scale/meta-info/hinted sessions either way).
    vod_cache_enabled: bool = True
    vod_cache_bytes: int = 268_435_456     # LRU byte budget (host + HBM)
    vod_cache_window_samples: int = 64     # samples packed per window
    vod_cache_lookahead_ms: int = 500      # pacer ring-fill horizon
    # keep each packed window's staged rows HBM-resident (uploaded once,
    # shared by every subscriber on that window) so a hot join's affine
    # prime pass costs zero H2D; host-only caching when off
    vod_cache_device: bool = True
    # --- DVR / time-shift (ISSUE 12: dvr/).  On: every pushed live
    # session's completed ring windows spill to
    # <movie_folder>/.dvr/<path>/ already in the fixed-slot packed
    # serving format (pack-at-record-time); live subscribers can PAUSE
    # and PLAY with Range: into the past (served by the VOD pacer from
    # the spill, catch-up rejoining live gapless), and stopping a
    # recording finalizes an instantly-servable <path>.dvr asset.
    # Requires vod_cache_enabled (the spill serves through the segment
    # cache's zero-repack open path).
    dvr_enabled: bool = False
    dvr_window_pkts: int = 64              # packets per spill window
    dvr_retention_bytes: int = 67_108_864  # per-track spill byte budget
    dvr_retention_sec: float = 600.0       # per-track spill duration cap
    # --- erasure-coded fleet storage (ISSUE 20: storage/).  On: every
    # FINALIZED .dvr asset is sharded into k data + m parity window
    # shards (the GF(256) engine's device matmul, host-oracle-checked)
    # striped across the live lease set under fenced Shard: claims; a
    # read missing <= m shards reconstructs transparently through the
    # spill chain's restore hook, scrub re-verifies local shards against
    # manifest crc32s, and a dead holder's shards are re-derived onto
    # ring successors as background math, not byte copies.  Requires
    # dvr_enabled; works single-node (all shards local — still gives
    # crc-scrubbed, reconstruct-on-corruption durability).
    storage_enabled: bool = False
    storage_data_shards: int = 4           # k: data shards per stripe
    storage_parity_shards: int = 2         # m: parity shards (loss budget)
    storage_scrub_interval_sec: float = 30.0
    storage_device: bool = True            # parity on device w/ host oracle
    # --- dynamic modules (QTSServer::LoadModules / module_folder pref)
    module_folder: str = ""            # "" = no dynamic modules
    # --- device tier
    tpu_fanout: bool = False           # batch engine instead of scalar loop
    tpu_min_outputs: int = 8           # below this the scalar loop wins
    # cross-stream megabatch scheduler (relay/megabatch.py): coalesce all
    # engine-eligible streams into one shape-bucketed device pass per pump
    # wake, with double-buffered H2D staging.  Off → every stream pays its
    # own per-wake device dispatch (the pre-ISSUE-4 behavior).
    megabatch_enabled: bool = True
    # below this many engine-eligible streams the coalescing overhead
    # isn't worth a stacked pass; per-stream stepping is used as-is
    megabatch_min_streams: int = 2
    # devices the megabatch serves from (ISSUE 7): 1 = the default
    # single-device dispatch; N > 1 = shard each shape bucket's stream
    # axis over the first N local devices (parallel.mesh src-only mesh);
    # 0 = every local device.  Clamped to what the box actually has —
    # a 1-device box always degrades to the single-device path
    megabatch_devices: int = 1
    # shared UDP egress pair for players (RTPSocketPool/UDPDemuxer shape;
    # required by the native sendmmsg/GSO fan-out). Falls back to per-client
    # port pairs when off or when the native core is unavailable.
    shared_udp_egress: bool = True
    # egress backend ladder (ISSUE 8): "auto" = best rung the boot-time
    # capability probe grants (io_uring with registered buffers/SQPOLL/
    # zerocopy where the kernel has it, the GSO/sendmmsg pair otherwise);
    # "io_uring"/"gso" force a rung (a forced-but-unavailable io_uring
    # degrades to gso with ONE egress.backend_fallback event); "scalar"
    # forces the per-datagram sendto baseline
    egress_backend: str = "auto"
    # x-Retransmit (reliable UDP) negotiation in SETUP — the reference's
    # reliable_udp pref (QTSServerPrefs; RTPStream.cpp:448 gate)
    reliable_udp: bool = True
    # --- lossy-WAN reliability tier (ISSUE 11: relay/fec.py).  On: every
    # plain-UDP subscriber gets a closed-loop FEC encoder (overhead 0
    # until its RRs report loss — a clean last mile costs nothing) and
    # the RFC 4585 generic-NACK → ring-bookmark RTX replay rung.  The
    # x-Retransmit reliable-UDP wrap supersedes it per output (its own
    # ack-driven resend window already owns that subscriber's loss).
    fec_enabled: bool = True
    fec_window: int = 16               # media packets per parity window
    fec_max_overhead: float = 0.30     # parity budget ceiling (ratio)
    fec_kind: str = "rs"               # rs | xor (xor caps parity at 1 row)
    fec_payload_type: int = 127        # parity packets' RTP PT
    rtx_payload_type: int = 126        # RTX replays' RTP PT
    rtx_budget_per_sec: float = 64.0   # per-output replay token refill
    rtx_burst: int = 32                # token bucket depth
    # device-side parity (host GF oracle checked per row; a mismatch
    # degrades the stream to host parity).  Off = host parity only.
    fec_device: bool = True
    # UDP push ingest via the native recvmmsg ring drain (one syscall per
    # 64 datagrams) instead of per-datagram asyncio callbacks; falls back
    # automatically when the native core is unavailable
    native_ingest: bool = True
    # --- cluster (EasyRedisModule / EasyCMS prefs)
    cloud_enabled: bool = False
    redis_host: str = "127.0.0.1"
    redis_port: int = 6379
    server_id: str = "easydarwin-tpu-0"
    cms_host: str = "127.0.0.1"
    cms_port: int = 10000
    wan_ip: str = "127.0.0.1"
    # --- fault-tolerant cluster tier (cluster/service.py: Redis leases +
    # fencing, consistent-hash placement, cross-server pull relay,
    # checkpoint-driven live session migration).  Supersedes the passive
    # cloud_enabled presence when on.
    cluster_enabled: bool = False
    cluster_lease_ttl_sec: float = 5.0     # lease TTL = failure-detect time
    cluster_heartbeat_sec: float = 1.0     # service tick cadence
    cluster_vnodes: int = 64               # ring points per node
    cluster_own_ttl_sec: float = 30.0      # Own:{path} record TTL
    cluster_migration_ttl_sec: float = 30.0  # Ckpt:{path} record TTL
    # cross-server pull relay envelope (cluster/pull.py)
    cluster_pull_connect_timeout_sec: float = 5.0
    cluster_pull_read_timeout_sec: float = 5.0   # no packet → stall
    cluster_pull_backoff_ms: float = 200.0       # first retry (doubles)
    cluster_pull_backoff_cap_ms: float = 5000.0
    cluster_pull_jitter_frac: float = 0.25       # ± anti-stampede jitter
    cluster_pull_breaker_failures: int = 5       # consecutive → open
    cluster_pull_breaker_open_sec: float = 10.0
    # --- load-aware control plane (ISSUE 13: cluster/capacity.py + the
    # Rebalancer in cluster/service.py).  Each node publishes a capacity
    # score (boot-time self-bench of the relay fan-out path, in relayed
    # pkts/sec; pin it here with a value > 0 to skip the bench) plus
    # live utilization into its fenced lease record; the hash ring
    # weights vnode counts by capacity, new SETUPs past the admission
    # high-water mark answer 453 or a 305 redirect to the placement-
    # resolved edge, and the rebalancer drains a sustained-burning
    # node's hottest stream to the least-loaded peer.
    cluster_capacity_score: float = 0.0          # 0 = boot self-bench
    cluster_admission_enabled: bool = True
    cluster_admission_high_water: float = 0.85   # util ratio gate
    cluster_rebalance_enabled: bool = True
    cluster_rebalance_high_water: float = 0.9    # sustained-burn level
    cluster_rebalance_low_water: float = 0.5     # target headroom gate
    cluster_rebalance_burn_sec: float = 10.0     # sustained-burn window
    cluster_rebalance_cooldown_sec: float = 30.0  # min gap between moves
    # --- auth / misc
    auth_enabled: bool = False
    rest_username: str = "admin"
    rest_password: str = "admin"
    rtsp_auth_enabled: bool = False
    users_file: str = ""               # qtpasswd-style user:realm:ha1
    auth_scheme: str = "digest"        # digest | basic
    max_connections: int = 20000       # epollEvent.cpp:16 MAX_EPOLL_FD
    # per-IP cap (QTSSSpamDefenseModule num_conns_per_ip; 0 = unlimited,
    # matching the reference's Linux build which omits the module)
    max_connections_per_ip: int = 0
    # --- SLO watchdog (obs/slo.py: multi-window burn-rate budgets over
    # the obs families, evaluated once per pump maintenance tick)
    slo_enabled: bool = True
    slo_latency_objective_ms: float = 50.0   # a good packet hits the wire…
    slo_latency_target: float = 0.99         # …within this for 99% of them
    slo_drop_objective: float = 0.01         # budgeted bad-packet fraction
    slo_fast_window_sec: float = 60.0
    slo_slow_window_sec: float = 600.0
    slo_fast_burn: float = 14.0              # SRE-workbook page-tier rates
    slo_slow_burn: float = 2.0
    slo_min_events: int = 200                # below this a window is noise
    # --- resilience (easydarwin_tpu/resilience/: deterministic fault
    # injection, health-driven degradation ladder, session checkpoint)
    resilience_enabled: bool = True          # degradation ladder active
    # FaultPlan spec armed at startup (chaos testing), e.g.
    # "seed=7,ingest_drop=0.05,egress_enobufs_every=300"; "" = none
    resilience_fault_plan: str = ""
    resilience_recover_sec: float = 10.0     # clean time per rung climbed
    resilience_max_retries: int = 3          # device retries before a drop
    resilience_backoff_ms: float = 250.0     # first retry backoff (doubles)
    # session checkpoint/hot-restore (<log_folder>/ckpt/): off by default
    # — a restore resurrects sessions from the PREVIOUS process, which an
    # operator opts into (the supervisor deployment), not a test run
    # sharing /tmp state
    resilience_checkpoint_enabled: bool = False
    resilience_checkpoint_interval_sec: float = 5.0
    # a checkpoint older than this is ignored at startup (stale files
    # must not resurrect long-dead sessions)
    resilience_checkpoint_max_age_sec: float = 60.0
    # --- status (RunServer.cpp:248-483: -S console + server_status file)
    stats_interval_sec: int = 0        # 0 = console display off
    status_file_path: str = ""         # "" = no status file
    status_file_interval_sec: int = 10
    # --- logging (QTSSRollingLog / AccessLog / ErrorLog prefs)
    log_folder: str = "/tmp/edtpu_logs"
    access_log_enabled: bool = True
    error_log_verbosity: str = "info"  # fatal|warning|info|debug

    _listeners: list[Callable[["ServerConfig"], None]] = field(
        default_factory=list, repr=False, compare=False)

    # -- reread-prefs machinery -------------------------------------------
    def on_change(self, fn: Callable[["ServerConfig"], None]) -> None:
        self._listeners.append(fn)

    def update(self, **kw) -> None:
        """Apply new values and rebroadcast (the RereadPrefs role)."""
        for k, v in kw.items():
            if k.startswith("_") or not hasattr(self, k):
                raise KeyError(f"unknown pref {k!r}")
            cur = getattr(self, k)
            setattr(self, k, type(cur)(v) if cur is not None else v)
        for fn in list(self._listeners):
            fn(self)

    # -- (de)serialization -------------------------------------------------
    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if not f.name.startswith("_")}

    @classmethod
    def from_dict(cls, d: dict) -> "ServerConfig":
        known = {f.name for f in dataclasses.fields(cls) if not f.name.startswith("_")}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_toml(cls, path: str) -> "ServerConfig":
        with open(path, "rb") as f:
            return cls.from_dict(tomllib.load(f))

    def to_toml(self) -> str:
        out = []
        for k, v in self.to_dict().items():
            if isinstance(v, bool):
                out.append(f"{k} = {'true' if v else 'false'}")
            elif isinstance(v, (int, float)):
                out.append(f"{k} = {v}")
            else:
                out.append(f'{k} = "{v}"')
        return "\n".join(out) + "\n"

    # -- derived -----------------------------------------------------------
    def egress_backend_choice(self) -> str:
        """The validated ``egress_backend`` pref.  A typo'd backend must
        fail the boot loudly — silently serving from a rung the operator
        didn't pick would void every forced-backend soak."""
        from ..relay.fanout import EGRESS_BACKENDS
        v = self.egress_backend.strip().lower()
        if v not in EGRESS_BACKENDS:
            raise ValueError(
                f"egress_backend {self.egress_backend!r} not one of "
                f"{EGRESS_BACKENDS}")
        return v

    def slo_config(self):
        from ..obs.slo import SloConfig
        return SloConfig(
            latency_objective_ms=self.slo_latency_objective_ms,
            latency_target=self.slo_latency_target,
            drop_objective=self.slo_drop_objective,
            fast_window_s=self.slo_fast_window_sec,
            slow_window_s=self.slo_slow_window_sec,
            fast_burn=self.slo_fast_burn,
            slow_burn=self.slo_slow_burn,
            min_events=self.slo_min_events)

    def cluster_config(self):
        from ..cluster.pull import PullConfig
        from ..cluster.service import ClusterConfig
        return ClusterConfig(
            self.server_id, ip=self.wan_ip,
            lease_ttl_sec=self.cluster_lease_ttl_sec,
            heartbeat_sec=self.cluster_heartbeat_sec,
            vnodes=self.cluster_vnodes,
            own_ttl_sec=self.cluster_own_ttl_sec,
            migration_ttl_sec=self.cluster_migration_ttl_sec,
            rebalance_enabled=self.cluster_rebalance_enabled,
            rebalance_high_water=self.cluster_rebalance_high_water,
            rebalance_low_water=self.cluster_rebalance_low_water,
            rebalance_burn_sec=self.cluster_rebalance_burn_sec,
            rebalance_cooldown_sec=self.cluster_rebalance_cooldown_sec,
            admission_enabled=self.cluster_admission_enabled,
            admission_high_water=self.cluster_admission_high_water,
            pull=PullConfig(
                connect_timeout_sec=self.cluster_pull_connect_timeout_sec,
                read_timeout_sec=self.cluster_pull_read_timeout_sec,
                backoff_ms=self.cluster_pull_backoff_ms,
                backoff_cap_ms=self.cluster_pull_backoff_cap_ms,
                jitter_frac=self.cluster_pull_jitter_frac,
                breaker_failures=self.cluster_pull_breaker_failures,
                breaker_open_sec=self.cluster_pull_breaker_open_sec))

    def fec_config(self):
        """The validated reliability-tier config (raises at boot on a
        bad window/kind — a typo'd tier silently protecting nothing
        would void every lossy soak)."""
        from ..relay.fec import FecConfig
        return FecConfig(
            window=self.fec_window,
            max_overhead=self.fec_max_overhead,
            kind=self.fec_kind,
            payload_type=self.fec_payload_type,
            rtx_payload_type=self.rtx_payload_type,
            rtx_budget_per_sec=self.rtx_budget_per_sec,
            rtx_burst=self.rtx_burst,
            use_device=self.fec_device).validate()

    def ladder_config(self):
        from ..resilience.ladder import LadderConfig
        return LadderConfig(
            recover_sec=self.resilience_recover_sec,
            max_retries=self.resilience_max_retries,
            backoff_ms=self.resilience_backoff_ms)

    def fault_plan(self):
        """The armed FaultPlan, or None when no chaos spec is set.  A
        malformed spec raises at startup — a typo'd plan that silently
        injects nothing would void the chaos run it was meant to drive."""
        if not self.resilience_fault_plan.strip():
            return None
        from ..resilience.inject import FaultPlan
        return FaultPlan.parse(self.resilience_fault_plan)

    def stream_settings(self):
        from ..relay.stream import StreamSettings
        return StreamSettings(
            bucket_size=self.bucket_size,
            bucket_delay_ms=self.bucket_delay_ms,
            overbuffer_ms=int(self.overbuffer_sec * 1000),
            max_age_ms=int(self.max_packet_age_sec * 1000),
            ring_capacity=self.ring_capacity)


# -- reference easydarwin.xml migration --------------------------------------

def _bool(v: str) -> bool:
    """Strict DSS bool: anything but true/false is reported, not coerced
    (a hand-edited 'True'/'1' must not silently become False)."""
    if v == "true":
        return True
    if v == "false":
        return False
    raise ValueError(f"not a DSS bool: {v!r}")


def _verbosity(v: str) -> str:
    i = int(v)
    if not 0 <= i <= 4:                 # DSS levels 0..4; reject garbage
        raise ValueError(f"verbosity {v!r} out of range")
    return ("fatal", "warning", "info", "info", "debug")[i]


#: reference pref name → (our field, converter).  Server-level prefs plus
#: the per-module sections users actually tune (QTSServerPrefs.cpp:190-280,
#: ReflectorStream::Register, EasyRedisModule prefs).
_XML_SERVER_MAP = {
    "rtsp_port": ("rtsp_port", int),                 # LIST-PREF: first value
    "service_lan_port": ("service_port", int),
    # http_service_port is DSS's RTSP-over-HTTP tunneling port, NOT the
    # REST service port — tunneling here rides the RTSP port itself, so
    # the pref is intentionally left unmapped
    "service_wan_ip": ("wan_ip", str),
    "bind_ip_addr": ("bind_ip",
                     lambda v: "0.0.0.0" if v in ("", "0") else v),
    "movie_folder": ("movie_folder", str),
    "maximum_connections": ("max_connections", int),
    "rtsp_session_timeout": ("rtsp_timeout_sec", int),
    "enable_cloud_platform": ("cloud_enabled", _bool),
    "authentication_scheme": ("auth_scheme", str),
    "error_logfile_verbosity": ("error_log_verbosity", _verbosity),
    "monitor_stats_file_name": ("status_file_path", str),
    "monitor_stats_file_interval_seconds": ("status_file_interval_sec", int),
}

_XML_MODULE_MAP = {
    ("QTSSReflectorModule", "reflector_bucket_offset_delay_msec"):
        ("bucket_delay_ms", int),
    ("QTSSReflectorModule", "reflector_buffer_size_sec"):
        ("overbuffer_sec", float),
    ("QTSSReflectorModule", "timeout_broadcaster_session_secs"):
        ("push_timeout_sec", int),
    ("QTSSAccessLogModule", "request_logging"):
        ("access_log_enabled", _bool),
    ("EasyRedisModule", "redis_ip"): ("redis_host", str),
    ("EasyRedisModule", "redis_port"): ("redis_port", int),
    ("EasyCMSModule", "cms_ip"): ("cms_host", str),
    ("EasyCMSModule", "cms_port"): ("cms_port", int),
}


def load_reference_xml(path: str) -> tuple["ServerConfig", list[str]]:
    """Load the reference's ``easydarwin.xml`` (the DSS ``PREF``/``MODULE``
    DTD, ``PrefsSourceLib/XMLPrefsParser.cpp``) into a ``ServerConfig``.

    Returns ``(config, unmapped)`` — ``unmapped`` lists reference pref
    names with no counterpart here (thinning windows, reliable-UDP
    internals, … — tuned automatically in this implementation), so a
    migrating operator can see exactly what was dropped.
    """
    import xml.etree.ElementTree as ET

    root = ET.parse(path).getroot()
    cfg = ServerConfig()
    unmapped: list[str] = []
    monitor_enabled = False

    def pref_value(el, label: str) -> str:
        if el.tag == "LIST-PREF":
            vals = el.findall("VALUE")
            if len(vals) > 1:           # only the first value carries over
                unmapped.append(
                    f"{label} (extra values dropped: "
                    f"{[(v.text or '').strip() for v in vals[1:]]})")
            return (vals[0].text or "").strip() if vals else ""
        return (el.text or "").strip()

    def apply(el, label: str, ent) -> None:
        if ent is None:
            unmapped.append(label)
            return
        field, conv = ent
        raw = pref_value(el, label)
        try:
            setattr(cfg, field, conv(raw))
        except ValueError:              # mapped name, malformed value
            unmapped.append(f"{label} (invalid value {raw!r})")

    server = root.find("SERVER")
    for el in (server if server is not None else []):
        if el.tag not in ("PREF", "LIST-PREF"):
            continue
        name = el.get("NAME", "")
        if name == "enable_monitor_stats_file":
            monitor_enabled = pref_value(el, name) == "true"
            continue
        apply(el, name, _XML_SERVER_MAP.get(name))
    for mod in root.findall("MODULE"):
        mod_name = mod.get("NAME", "")
        for el in mod:
            if el.tag not in ("PREF", "LIST-PREF"):
                continue
            name = el.get("NAME", "")
            apply(el, f"{mod_name}/{name}",
                  _XML_MODULE_MAP.get((mod_name, name)))
    if not monitor_enabled:
        cfg.status_file_path = ""       # file name without the enable flag
    return cfg, unmapped
