"""Server assembly + supervision — the ``RunServer.cpp`` equivalent.

Boot order mirrors ``StartServer`` (``RunServer.cpp:65-215``): config →
session registry → listeners (RTSP + REST service port) → relay pump
(the ReflectorSocket/IdleTask send loop, here one asyncio task, woken by
ingest and ticking at ``reflect_interval_ms``) → timeout sweeper (15 s
granularity, ``TimeoutTask.h:66``) → optional cluster presence task.

The pump (``relay.pump``) chooses per stream between the scalar CPU
fan-out and the TPU batch engine (``relay.fanout.TpuFanoutEngine``) from
config, subscriber count and ladder rung — the "module loaded / unloaded
with CPU fallback" behavior the north star requires.
"""

from __future__ import annotations

import asyncio
import time

from .. import obs
from ..obs import PROFILER, TRACER, t0_of
from ..relay.fanout import TpuFanoutEngine
from ..relay.pump import Pump
from ..relay.session import SessionRegistry, now_ms
from .config import ServerConfig
from .rest import RestApi
from .rtsp import RtspServer


class _RestoredSubscriber:
    """Connection stand-in for a checkpoint-restored UDP subscriber.

    The real RTSP connection died with the previous process; this
    adapter duck-types what ``RtspServer.on_client_rtcp`` needs
    (``player_tracks``/``relay``/``path``/``stats``/``last_activity``)
    so the restored output's receiver reports keep driving quality
    adaptation AND proving liveness — and the sweep reaps the output
    after ``rtsp_timeout_sec`` of RTCP silence, so a player that never
    came back cannot be relayed to forever."""

    is_pusher = False

    def __init__(self, sess, track_id: int, stream, output):
        import types
        self.relay = sess
        self.path = sess.path
        self.stream = stream
        self.output = output
        self.player_tracks = {track_id: types.SimpleNamespace(
            output=output)}
        self.stats: dict = {}
        self.last_activity = time.monotonic()


class StreamingServer:
    def __init__(self, config: ServerConfig | None = None, *,
                 describe_fallback=None, redis_client=None):
        self.config = config or ServerConfig()
        self.registry = SessionRegistry(self.config.stream_settings())
        from ..vod.session import VodService
        self.vod = VodService(self.config.movie_folder)
        self.auth = None
        if self.config.rtsp_auth_enabled:
            from .auth import AuthService, AccessRules, UsersFile
            rules = AccessRules()
            rules.protect("/", [])          # valid-user everywhere by default
            self.auth = AuthService(
                UsersFile(self.config.users_file or None),
                rules, scheme=self.config.auth_scheme)
        self.access_log = None
        self.error_log = None
        if self.config.access_log_enabled:
            import os
            from ..utils.logs import AccessLog, ErrorLog
            self.access_log = AccessLog(
                os.path.join(self.config.log_folder, "access.log"))
            self.error_log = ErrorLog(
                os.path.join(self.config.log_folder, "error.log"),
                verbosity=self.config.error_log_verbosity)
        self.rtsp = RtspServer(self.config, self.registry,
                               describe_fallback=describe_fallback,
                               on_pump_wake=self._wake, vod=self.vod,
                               auth=self.auth, access_log=self.access_log)
        from ..relay.source import SdpFileRelaySource
        self.relay_source = SdpFileRelaySource(
            self.config.movie_folder, self.registry,
            on_ingest=lambda _path: self._wake())
        self.rtsp.relay_source = self.relay_source
        from ..relay.pull import PullRelayManager
        self.pulls = PullRelayManager(self.registry,
                                      on_packet=lambda _path: self._wake())
        self.rest = RestApi(self.config, self)
        from ..vod.record import RecordingManager
        from ..hls import HlsService
        from .mp3 import Mp3Service
        self.recordings = RecordingManager()
        self.hls = HlsService(self.registry,
                              requant_on_device=self.config.tpu_fanout)
        from ..models.mjpeg_ladder import MjpegTranscodeService
        self.transcodes = MjpegTranscodeService(
            self.registry, on_frame=lambda _path: self._wake())
        self.mp3 = Mp3Service(self.config.movie_folder)
        self.rtsp.http_get_handler = self._rtsp_port_http_get
        self._pump_event = asyncio.Event()
        #: first un-serviced wake's perf stamp — the wake→pass queueing
        #: delay phase (obs/profile.py); None = no wake pending
        self._wake_ns: int | None = None
        #: the wake in progress: (pump.wake span, its first clock read,
        #: wake→pass µs); closed by _wake_close after the maintenance
        #: block, or by the next _reflect_all (direct callers)
        self._wake_open_rec: tuple | None = None
        self._wake_seq = 0
        #: SLO watchdog over the obs families; the pump's 1 Hz
        #: maintenance block ticks it, violations flag flight recorders
        from ..obs import PROFILER, SloWatchdog
        self.slo = SloWatchdog(self.config.slo_config(),
                               offender=PROFILER.top_offender)
        #: degradation ladder (resilience/ladder.py): per-stream rung
        #: megabatch → per-stream device → CPU oracle → shed, consulted
        #: by the pump per wake and ticked by the 1 Hz maintenance block
        self.ladder = None
        if self.config.resilience_enabled:
            from ..resilience import DegradationLadder
            self.ladder = DegradationLadder(self.config.ladder_config())
            # RTX budget exhaustion (relay/fec.py) is charged to the
            # ladder: a black-holed client's NACK storm sheds load
            # through the same machinery as any other overload
            self.rtsp.on_rtx_giveup = (
                lambda path: self.ladder.note_device_error(
                    path, reason="rtx_giveup"))
        #: session checkpoint/hot-restore (resilience/checkpoint.py) —
        #: built in start() once log_folder is final
        self.checkpoint = None
        #: adapters owning hot-restored subscribers (RTCP demux +
        #: silence reaping); swept alongside the RTSP timeout sweep
        self._restored_subs: list[_RestoredSubscriber] = []
        #: parked interleaved-TCP checkpoint records (ISSUE 14):
        #: (path, track_id, session_id) → (record, parked_monotonic).
        #: Claimed by the rtsp SETUP re-attach hook; unclaimed entries
        #: age out via the sweep as counted ckpt.tcp_orphan events.
        self._pending_tcp: dict = {}
        self._armed_faults = False
        self._tasks: list[asyncio.Task] = []
        self._running = False
        self._restart_requested = False
        self.restart_event = asyncio.Event()
        #: io_uring egress ring over the shared UDP pair (ISSUE 8);
        #: built in start() by the probe ladder, None = GSO/scalar rung
        self.uring_egress = None
        #: the rung the probe ladder landed on ("io_uring"/"gso"/
        #: "scalar") — mirrored into egress_backend_info{backend}
        self.egress_backend_effective = "gso"
        #: the normalized ``egress_backend`` the probe ladder started from
        self._egress_backend_choice = "auto"
        #: pusher RTP sockets get multishot io_uring ingest when True
        self.uring_ingest_enabled = False
        #: the wake (relay/pump.py): routes every stream, owns the
        #: engines, the megabatch scheduler and its serving mesh
        #: (``pump.mesh``: built in start() so a bad device config fails
        #: loudly at boot, not on the first busy wake)
        self.pump = Pump(self.config, on_device=self._on_device,
                         new_engine=self._new_engine, ladder=self.ladder,
                         error_log=self.error_log)
        #: ``device.resolve`` result ({"platform","kind","count"}) —
        #: filled by start() when tpu_fanout is on, BEFORE any listener
        #: opens; None = the engine tier is off and JAX was not touched
        self.device_info: dict | None = None
        #: the boot in progress where ``main`` started this server
        #: (``obs.boot.BootPhases``); None for one started any other way
        self.boot = None
        #: VOD segment cache + shared group pacer (ISSUE 10): hot file
        #: sessions become megabatch-eligible relay streams the pump
        #: steps alongside live; built in start() (engines need the
        #: egress probe's verdict), None = every player runs the cold
        #: per-session FileSession
        self.vod_cache = None
        self.vod_pacer = None
        #: DVR / time-shift tier (ISSUE 12: dvr/): window spill off the
        #: live rings + pause/rewind/catch-up served by the VOD pacer;
        #: built in start() after the cache/pacer exist, None = off
        self.dvr = None
        #: async peer-fill plumbing: (path, track, win) -> Future of the
        #: helper-thread HTTP fetch (see _dvr_peer_fetch)
        self._dvr_fetches: dict = {}
        self._dvr_fetch_pool = None
        #: erasure-coded storage tier (ISSUE 20: storage/): finalized
        #: DVR assets sharded k+m across the fleet, reads reconstruct
        #: from any k survivors; built in start() after the DVR tier,
        #: None = off
        self.storage = None
        #: in-flight erasure restores: (path, track, win) -> Future of
        #: the helper-thread reconstruct (see _storage_restore)
        self._storage_fetches: dict = {}
        self._storage_scrub_due = 0.0
        self.started_at = time.time()
        from .status import StatusMonitor
        self.status = StatusMonitor(self)
        self.presence = None
        #: fault-tolerant cluster tier (cluster/service.py) — built in
        #: start() once the listener ports are known
        self.cluster = None
        #: load-aware control plane (ISSUE 13): capacity score + live
        #: utilization tracker, built in start() under cluster mode
        #: (the boot self-bench only runs when a cluster will read it)
        self.load_tracker = None
        #: remote DVR assets bootstrapped via /api/v1/dvrmeta:
        #: path -> (host, http_port, {track: [win_lo, win_hi]}) —
        #: consulted by _dvr_peer_fetch when the armed-asset Own:
        #: advertisement (cluster.dvr_peers) has no entry (a finalized
        #: asset's advert died with its live claim)
        self._dvr_meta_peers: dict = {}
        #: paths whose all-peer meta sweep found nothing: path ->
        #: monotonic retry-after.  Without this a repeat DESCRIBE of the
        #: same bogus .dvr path re-runs the full (N-1)-peer HTTP sweep
        #: every time — the path-scan amplification the live describe()
        #: gate exists to prevent
        self._dvr_meta_misses: dict = {}
        self._user_describe_fallback = describe_fallback
        self._redis_client = redis_client
        self.config.on_change(self._on_config_change)

    # ------------------------------------------------------------- control
    @property
    def modules(self):
        return self.rtsp.modules

    def register_module(self, module) -> None:
        """QTSS_Register + AddModule equivalent."""
        self.rtsp.modules.register(module)

    async def start(self) -> None:
        self._running = True
        # crash flight dumps land next to this server's rolling logs
        # (written only when a dump happens; write failures swallowed).
        # The recorder — like REGISTRY/TRACER/EVENTS — is process-global,
        # so only a server actually STARTING claims the directory; a
        # merely-constructed instance never redirects a running one's
        import os
        from ..obs import FLIGHT, set_node
        FLIGHT.dump_dir = os.path.join(self.config.log_folder, "flight")
        # claim the process-wide node identity for event/flight
        # attribution (ISSUE 15) — same starting-server-wins rule as the
        # dump dir; the cluster heartbeat refreshes the fence token
        set_node(self.config.server_id)
        # plugins register before the listeners accept anything, so their
        # filter/authorize hooks cover every request (the reference loads
        # modules before CreateListeners' ports go live too)
        if self.config.module_folder:
            from .modules import load_modules_from
            for m in load_modules_from(
                    self.config.module_folder,
                    on_error=lambda f, e: self.error_log
                    and self.error_log.warning(f"module {f} failed: {e}")):
                self.register_module(m)
        if self.config.fec_enabled:
            self.config.fec_config()    # raises at boot on a bad window/kind
        if self.config.tpu_fanout:
            self._resolve_engine_tier()
            self._boot_phase("listen", devices=self.device_info["count"])
        else:
            self._boot_phase("listen")
        # chaos plan (resilience/inject.py): armed before anything serves
        # so the very first pass already runs under the fault schedule
        plan = self.config.fault_plan()
        if plan is not None:
            from ..resilience import INJECTOR
            INJECTOR.arm(plan)
            self._armed_faults = True
        await self.rtsp.start()
        self._init_egress_backend()
        await self.rest.start()
        if self.config.resilience_checkpoint_enabled:
            # hot-restore AFTER the egress pair exists (restored UDP
            # subscribers send through it) and BEFORE the pump starts
            from ..resilience import CheckpointManager
            self.checkpoint = CheckpointManager(
                os.path.join(self.config.log_folder, "ckpt"),
                interval_sec=self.config.resilience_checkpoint_interval_sec,
                max_age_sec=self.config.resilience_checkpoint_max_age_sec)
            self.rtsp.tcp_restore = self.claim_tcp_restore
            try:
                n_sess, n_out = self.checkpoint.restore(
                    self.registry, output_factory=self._restored_output,
                    tcp_sink=self._park_tcp_record)
                if n_out:
                    self._adopt_restored_outputs()
                if n_sess and self.error_log:
                    self.error_log.info(
                        f"checkpoint: restored {n_sess} sessions / "
                        f"{n_out} subscribers")
            except Exception as e:
                if self.error_log:
                    self.error_log.warning(f"checkpoint restore: {e!r}")
        self.rtsp.modules.run_initialize(self)
        if (self.config.tpu_fanout and self.config.megabatch_enabled
                and self.config.megabatch_devices != 1):
            # the megabatch serving mesh (ISSUE 7): built before the
            # pump's first wake.  A mesh that cannot be built — an
            # exception, or fewer devices than megabatch_devices asked
            # for — serves single-device, COUNTED and logged
            # (device_errors_swallowed_total{site="megabatch_mesh"}),
            # never quietly
            from ..device import note_swallowed
            from ..parallel.mesh import make_megabatch_mesh
            want = self.config.megabatch_devices
            mesh = None
            try:
                mesh = make_megabatch_mesh(want)
                if mesh is None and want > 1:
                    raise RuntimeError(
                        f"megabatch_devices={want} but only "
                        f"{self.device_info['count']} device(s) present")
            except Exception as e:
                mesh = None
                note_swallowed("megabatch_mesh", e)
                if self.error_log:
                    self.error_log.warning(
                        f"megabatch mesh unavailable, serving "
                        f"single-device: {e!r}")
            self.pump.mesh = mesh
            if mesh is not None and self.error_log:
                from ..parallel.distributed import process_span
                self.error_log.info(f"megabatch mesh: {process_span(mesh)}")
        if self.config.vod_cache_enabled:
            from ..vod.cache import SegmentCache
            from ..vod.session import VodPacerGroup
            self.vod_cache = SegmentCache(
                budget_bytes=self.config.vod_cache_bytes,
                window_samples=self.config.vod_cache_window_samples,
                device=self.config.vod_cache_device)
            self.vod_pacer = VodPacerGroup(
                self.vod_cache,
                engine_for=self.pump.engine_for,
                engine_drop=self.pump.engine_drop,
                scheduler=lambda: self.pump.megabatch,
                settings=self.config.stream_settings(),
                lookahead_ms=self.config.vod_cache_lookahead_ms,
                device_prime=(self.config.vod_cache_device
                              and self.config.tpu_fanout))
            self.rtsp.vod_pacer = self.vod_pacer
            if self.checkpoint is not None:
                # re-warm the previous process's hot set (PR 5 shape:
                # metadata only — windows re-pack in the background on
                # each asset's first open)
                import json
                self._vod_ckpt_path = os.path.join(
                    self.config.log_folder, "ckpt", "vod_cache.json")
                try:
                    with open(self._vod_ckpt_path,
                              encoding="utf-8") as fh:
                        n = self.vod_cache.restore(json.load(fh))
                    if n and self.error_log:
                        self.error_log.info(
                            f"vod cache: re-warming {n} windows")
                except (OSError, ValueError):
                    pass
        if self.config.dvr_enabled:
            if self.vod_pacer is None:
                if self.error_log:
                    self.error_log.warning(
                        "dvr_enabled needs vod_cache_enabled (the spill "
                        "serves through the segment cache); DVR is OFF")
            else:
                from ..dvr import DvrManager
                self.dvr = DvrManager(
                    os.path.join(self.config.movie_folder, ".dvr"),
                    self.vod_cache, self.vod_pacer, self.registry,
                    window_pkts=self.config.dvr_window_pkts,
                    retention_bytes=self.config.dvr_retention_bytes,
                    retention_sec=self.config.dvr_retention_sec,
                    error_log=self.error_log)
                self.rtsp.dvr = self.dvr
        if self.config.storage_enabled:
            if self.dvr is None:
                if self.error_log:
                    self.error_log.warning(
                        "storage_enabled needs dvr_enabled (only "
                        "finalized DVR assets are sharded); storage is "
                        "OFF")
            else:
                from ..storage import StorageService
                self.storage = StorageService(
                    os.path.join(self.config.movie_folder, ".shards"),
                    self.config.server_id,
                    k=self.config.storage_data_shards,
                    m=self.config.storage_parity_shards,
                    use_device=self.config.storage_device,
                    error_log=self.error_log)
                self.dvr.on_finalize = self._storage_on_finalize
                self.dvr.restorer = self._storage_restore
        # crash-safe recorder orphan sweep (vod/record.py): leftover
        # <file>.mp4.tmp means a recorder died mid-write — report it
        from ..vod.record import sweep_orphans
        try:
            sweep_orphans(self.config.movie_folder)
        except OSError:
            pass
        self._tasks = [
            asyncio.create_task(self._pump_loop(), name="relay-pump"),
            asyncio.create_task(self._sweep_loop(), name="timeout-sweep"),
        ]
        if self.config.stats_interval_sec or self.config.status_file_path:
            self._tasks.append(
                asyncio.create_task(self._status_loop(), name="status"))
        if self.config.cluster_enabled:
            # the fault-tolerant tier: lease + placement + pull relay +
            # migration.  It subsumes the passive presence records, so
            # cloud_enabled presence is skipped when it runs.
            from ..cluster.redis_client import AsyncRedis
            from ..cluster.service import ClusterService
            redis = self._redis_client or AsyncRedis(
                self.config.redis_host, self.config.redis_port)
            ccfg = self.config.cluster_config()
            ccfg.rtsp_port = self.rtsp.port or self.config.rtsp_port
            ccfg.http_port = self.rest.port or self.config.service_port
            self.cluster = ClusterService(
                redis, ccfg, registry=self.registry,
                pull_manager=self.pulls,
                restore_doc=self._cluster_restore,
                on_pull_failure=self._on_pull_failure,
                on_fence_lost=self._cluster_fence_lost,
                error_log=self.error_log)
            if self.dvr is not None:
                # spilled-window spans ride this node's fenced Own:
                # records; cold DVR windows another node recorded
                # peer-fill through its spill files, not origin
                self.cluster.dvr_advertise = self.dvr.advertise
                self.dvr.fetcher = self._dvr_peer_fetch
                # fully-remote asset bootstrap (ISSUE 13 satellite):
                # a .dvr DESCRIBE on a node that never saw the stream
                # syncs the recording node's meta/index documents first
                self.dvr.meta_sync = self._dvr_meta_sync
            if self.storage is not None:
                # the erasure tier rides the cluster: shards place on
                # the capacity-weighted ring, claims write through the
                # tick as fenced Shard: records, and repair watches the
                # live lease set for dead holders (ISSUE 20)
                self.storage.node_id = ccfg.node_id
                self.storage.peer_nodes = \
                    lambda: dict(self.cluster.last_nodes) \
                    if self.cluster is not None else {}
                self.storage.ring_for = self.cluster.placement.ring
                self.storage.push_shard = self._storage_push_blocking
                self.storage.fetch_shard = self._storage_fetch_blocking
                self.storage.fetch_manifest = \
                    self._storage_manifest_blocking
                self.cluster.storage_claims = \
                    self.storage.pending_claims
                self.cluster.storage_repair = self.storage.repair_scan
            # load-aware control plane (ISSUE 13): capacity published
            # into the lease each heartbeat, admission gate on new
            # SETUPs.  The self-bench is cached per boot; an operator-
            # pinned cluster_capacity_score skips it entirely.
            from ..cluster.capacity import LoadTracker, self_bench
            cap = self.config.cluster_capacity_score or self_bench()
            self.load_tracker = LoadTracker(
                cap,
                slo=self.slo if self.config.slo_enabled else None,
                subscribers=lambda: sum(
                    s.num_outputs
                    for s in self.registry.sessions.values()))
            self.cluster.load_status = self.load_tracker.sample
            if ccfg.admission_enabled:
                self.rtsp.admission = self._admission_verdict
            # fleet federation (ISSUE 15): the rollup published into
            # Fleet:{node} each heartbeat, and the gate that lets live
            # peers' pulls thread their trace ids into this node
            from ..obs import fleet as fleet_mod
            self.cluster.fleet_status = \
                lambda: fleet_mod.build_rollup(self)
            self.rtsp.peer_trace_gate = self._peer_trace_gate
            await self.cluster.start()
            self.rtsp.describe_fallback = self._cluster_describe
        elif self.config.cloud_enabled:
            from ..cluster.presence import PresenceService
            from ..cluster.redis_client import AsyncRedis
            redis = self._redis_client or AsyncRedis(
                self.config.redis_host, self.config.redis_port)
            self.presence = PresenceService(
                redis, self.config.server_id, ip=self.config.wan_ip,
                rtsp_port=self.rtsp.port or self.config.rtsp_port,
                http_port=self.rest.port or self.config.service_port)
            try:
                await self.presence.start()
            except Exception:
                self.presence = None       # redis unreachable: run standalone

    async def stop(self) -> None:
        self._running = False
        if self.checkpoint is not None:
            # final snapshot while the registry is still intact, so a
            # supervisor relaunch (EXIT_RESTART) resumes from the very
            # last state, not the last periodic interval
            try:
                self.checkpoint.write(self.registry)
                if self.vod_cache is not None:
                    self._write_vod_cache_meta()
            except Exception:
                pass
        if self._armed_faults:
            from ..resilience import INJECTOR
            INJECTOR.disarm()
            self._armed_faults = False
        self.rtsp.modules.run_shutdown(self)
        if self.cluster is not None:
            # planned drain: fresh checkpoints published + lease released
            # while the registry is still intact, so peers adopt within
            # one tick instead of a TTL wait
            try:
                await self.cluster.stop(drain=True)
            except Exception:
                pass
            self.cluster = None
            self.rtsp.admission = None
            self.load_tracker = None
        if self.presence is not None:
            await self.presence.stop()
            self.presence = None
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        # drain the recorder tier while sessions still exist: every
        # in-flight MP4 finalizes (tmp→rename, playable moov) and every
        # armed DVR asset flips complete — instant stream-to-VOD instead
        # of an orphan sweep at next boot
        try:
            self.recordings.stop_all()
        except Exception:
            pass
        if self.dvr is not None:
            try:
                self.dvr.close()
            except Exception:
                pass
            self.rtsp.dvr = None
            self.dvr = None
        if self.storage is not None:
            try:
                self.storage.close()
            except Exception:
                pass
            self.storage = None
            self._storage_fetches.clear()
        if self._dvr_fetch_pool is not None:
            self._dvr_fetch_pool.shutdown(wait=False, cancel_futures=True)
            self._dvr_fetch_pool = None
            self._dvr_fetches.clear()
        if self.vod_pacer is not None:
            self.rtsp.vod_pacer = None
            try:
                self.vod_pacer.close()
                self.vod_cache.close()
            except Exception:
                pass
            self.vod_pacer = None
            self.vod_cache = None
        self.relay_source.close_all()
        self.transcodes.stop_all()
        await self.pulls.stop_all()
        await self.rtsp.stop()
        # the native sender thread goes with the server (no wake is
        # running: every job it was handed has been settled)
        from .. import native
        native.sender_stop()
        if self.uring_egress is not None:
            self.uring_egress.close()
            self.uring_egress = None
        if self.uring_ingest_enabled:
            from .. import native
            native.uring_ingest_disarm()
            self.uring_ingest_enabled = False
        await self.rest.stop()

    def _resolve_engine_tier(self) -> None:
        """tpu_fanout is on: decide NOW what the engine runs on, before
        a listener opens.  The native core must build from this tree and
        load (``native.require``) and the backend must be a TPU unless
        ``JAX_PLATFORMS`` asked for the CPU by name (``device.resolve``)
        — either failure raises and the boot stops.  Leaving this to
        the first ``eng.step`` would hand a backend-init failure to the
        per-stream guard and the degradation ladder, and the process
        would serve from the scalar loop behind a ``tpu_fanout=on``
        banner."""
        from .. import device, native
        self._boot_phase("native")
        native.require()
        self._boot_phase(
            "backend", built=int(native.build_info()["built_this_process"]))
        self.device_info = device.resolve(require_tpu=True)
        if self.error_log:
            self.error_log.info("engine tier: " + self.engine_banner())

    def _boot_phase(self, phase: str, **ended) -> None:
        """Where ``main`` started this server: the boot goes on to
        ``phase`` (``obs.boot``)."""
        if self.boot is not None:
            self.boot.enter(phase, **ended)

    def engine_banner(self) -> str:
        """``platform=… device_kind=… devices=… native=…`` — the boot
        line's and the error log's account of what serves the engine."""
        from .. import native
        d = self.device_info
        if d is None:
            return "platform=none (tpu_fanout off)"
        nb = native.build_info()
        return (f"platform={d['platform']} device_kind=\"{d['kind']}\" "
                f"devices={d['count']} native_src={nb['source_digest']} "
                f"native_built_at_boot={int(nb['built_this_process'])}")

    def request_restart(self) -> None:
        """REST /restart: under the supervisor (server.supervisor) the main
        loop exits with EXIT_RESTART and the watchdog relaunches."""
        self._restart_requested = True
        self.restart_event.set()

    def _on_config_change(self, cfg: ServerConfig) -> None:
        self.registry.settings = cfg.stream_settings()
        self.rtsp.modules.run_reread_prefs(cfg)

    def _wake(self) -> None:
        if self._wake_ns is None:
            self._wake_ns = time.perf_counter_ns()
        self._pump_event.set()

    def _restored_output(self, rec: dict):
        """Checkpoint output factory: rebuild a UDP subscriber on the
        shared egress pair (the address pair IS the transport — the
        client never learns the server restarted).  Interleaved/TCP
        outputs died with their connections and are skipped."""
        if rec.get("kind") != "udp" or not rec.get("rtp_addr"):
            return None
        egress = self.rtsp.shared_egress
        if egress is None or not egress.active:
            return None
        from .egress import NativeUdpOutput
        ip, rtp_port = rec["rtp_addr"]
        rtcp = rec.get("rtcp_addr") or (ip, int(rtp_port) + 1)
        out = NativeUdpOutput(egress, ip, int(rtp_port), int(rtcp[1]))
        # the RTCP destination may live on a DIFFERENT host than the RTP
        # one (RTSP Transport destination semantics) — restore it whole
        out.rtcp_addr = (rtcp[0], int(rtcp[1]))
        return out

    def _adopt_restored_outputs(self, paths=None, exclude_ids=()) -> None:
        """Give every just-restored UDP output a connection stand-in:
        register it with the shared-egress RTCP demux (quality feedback
        + liveness proof flow again) and track it for the silence sweep.
        At startup every output in the registry IS a restored one; a
        mid-run migration restore passes ``paths`` (the restored
        sessions) and ``exclude_ids`` (outputs that existed before the
        restore) so live subscribers are never double-registered."""
        egress = self.rtsp.shared_egress
        if egress is None:
            return
        exclude = set(exclude_ids)
        for sess in self.registry.sessions.values():
            if paths is not None and sess.path not in paths:
                continue
            for tid, stream in sess.streams.items():
                for out in stream.outputs:
                    if getattr(out, "native_addr", None) is None \
                            or id(out) in exclude:
                        continue
                    sub = _RestoredSubscriber(sess, tid, stream, out)
                    self._restored_subs.append(sub)
                    egress.register(out, sub)

    def _cluster_restore(self, doc: dict) -> tuple[int, int]:
        """Cluster migration hook: rebuild the adopted stream's sessions
        + UDP subscribers from its Redis-published checkpoint.  The
        subscribers' address pairs ARE their transport, so the players
        are re-pointed at this node without re-SETUP.  Interleaved-TCP
        subscribers park for the re-attach path (their connection died
        with the old owner; the player reconnects and presents its old
        Session id — ISSUE 14 migration parity)."""
        from ..resilience.checkpoint import restore_registry
        if self.rtsp.tcp_restore is None:
            self.rtsp.tcp_restore = self.claim_tcp_restore
        paths = {s.get("path") for s in doc.get("sessions", ())}
        pre = {id(o)
               for p in paths if p
               for sess in (self.registry.find(p),) if sess is not None
               for st in sess.streams.values() for o in st.outputs}
        n_sess, n_out = restore_registry(
            self.registry, doc, output_factory=self._restored_output,
            tcp_sink=self._park_tcp_record)
        # trace lineage (ISSUE 15): the adopted streams now live HERE —
        # extend their node lineage so a stitched trace names both the
        # dead owner and this adopter under the one preserved trace id
        for p in paths:
            sess = self.registry.find(p) if p else None
            if sess is not None and (not sess.trace_nodes
                                     or sess.trace_nodes[-1]
                                     != self.config.server_id):
                sess.trace_nodes.append(self.config.server_id)
        if n_out:
            self._adopt_restored_outputs(paths=paths, exclude_ids=pre)
        self._wake()
        return n_sess, n_out

    def _on_pull_failure(self, path: str) -> None:
        """Cluster pull envelope → ladder coupling: an upstream pull
        failure degrades the stream's rung, never kills the session."""
        if self.ladder is not None:
            self.ladder.note_device_error(path, reason="pull_errors")

    def _cluster_fence_lost(self, path: str) -> None:
        """A NEWER owner fenced us out of ``path``: stop serving it on
        THIS node.  Dropping only the Redis claim would leave a zombie
        data plane — two nodes transmitting the same ssrc to the same
        subscribers.  The local source connection is closed (the device
        re-registers and re-pushes to the new owner — the reference
        recovery protocol), restored stand-ins are unregistered and the
        session removed."""
        sess = self.registry.find(path)
        if sess is None:
            return
        egress = self.rtsp.shared_egress
        for sub in [s for s in self._restored_subs if s.path == path]:
            self._restored_subs.remove(sub)
            if egress is not None:
                egress.unregister(sub.output, sub)
        from ..relay.pull import _spawn_cleanup
        for conn in [c for c in list(self.rtsp.connections)
                     if c.is_pusher and c.path == path]:
            if conn.writer is not None:
                try:
                    conn.writer.close()
                except Exception:
                    pass
            _spawn_cleanup(conn.close())
        if self.registry.find(path) is sess:
            self.registry.remove(path)

    async def _cluster_describe(self, path: str):
        """DESCRIBE fallback under cluster mode: a path another node
        owns is served locally through the pull envelope; any
        user-supplied fallback still gets the last word."""
        text = None
        if self.cluster is not None:
            try:
                text = await self.cluster.describe(path)
            except Exception as e:
                if self.error_log:
                    self.error_log.warning(f"cluster describe: {e!r}")
        if text is None and self._user_describe_fallback is not None:
            text = await self._user_describe_fallback(path)
        return text

    def _peer_trace_gate(self, node_id: str, client_ip: str) -> bool:
        """X-Trace-Id acceptance (ISSUE 15): the request must name a
        LIVE-leased cluster node in X-Cluster-Node AND arrive from that
        node's registered lease address — node ids are public (the
        fleet endpoint lists them), so the name alone is forgeable; the
        source address binds the claim to the peer's actual socket.
        (Co-located nodes sharing one address — the test topology —
        still cannot be forged from off-box.)"""
        if not node_id or self.cluster is None:
            return False
        meta = self.cluster.last_nodes.get(node_id)
        return isinstance(meta, dict) and meta.get("ip") == client_ip

    def _admission_verdict(self, path: str, client_key: str
                           ) -> tuple[str, str | None] | None:
        """Overload admission (ISSUE 13): None = admit; otherwise
        ``("redirect", url)`` — a placement-resolved edge exists, send
        RTSP 305 — or ``("refuse", None)`` — RTSP 453.  Synchronous by
        design: it reads the LAST heartbeat's load sample and node
        snapshot (a SETUP must never wait on Redis); the
        ``overload_spoof`` fault site forces the verdict for chaos
        runs.  Shedding before burning: every refusal is counted and
        evented."""
        lt = self.load_tracker
        if lt is None:
            return None
        from .. import obs
        from ..resilience import INJECTOR
        hw = self.config.cluster_admission_high_water
        over = lt.last_util >= hw
        if not over and INJECTOR.active:
            over = INJECTOR.overload_spoof()
        if not over:
            return None
        target = None
        url = None
        cl = self.cluster
        if cl is not None and cl.last_nodes:
            target = cl.placement.edge_for(
                path, cl.last_nodes, client_key=client_key,
                exclude=(cl.config.node_id,), high_water=hw)
            if target is not None:
                meta = cl.last_nodes.get(target) or {}
                ip, port = meta.get("ip"), meta.get("rtsp")
                if ip and port:
                    p = path if path.startswith("/") else "/" + path
                    url = f"rtsp://{ip}:{int(port)}{p}"
        action = "redirect" if url else "refuse"
        obs.CLUSTER_ADMISSION_REFUSED.inc(action=action)
        from ..obs import EVENTS
        EVENTS.emit("cluster.refuse", level="warn", stream=path,
                    action=action, util=round(lt.last_util, 3),
                    target=target)
        return (action, url)

    #: in-flight DVR peer fetches we will still collect (bound: a slow
    #: peer must not accumulate unbounded queued HTTP work)
    _DVR_FETCH_INFLIGHT_MAX = 32
    #: seconds an all-peer /api/v1/dvrmeta miss stays cached (a newly
    #: finalized recording becomes peer-fillable within this bound)
    _DVR_META_MISS_SEC = 10.0

    def _dvr_peer_fetch(self, path: str, track_id: int,
                        win: int) -> bytes | None:
        """Cluster peer-fill: fetch one spilled window blob from the
        node whose fenced ``Own:`` record advertises it (the recording
        node serves it over REST ``/api/v1/dvrwindow``).  The caller is
        the segment cache's packed-fill path, INLINE ON THE PUMP — so
        the HTTP round-trip runs on a helper thread and this returns
        ``b""`` (fetch pending: retry next tick, the time-shift cursor
        HOLDS) until the result lands; ``None`` means definitively
        unavailable (no peer / outside the advertised span / fetch
        failed) and the cursor hops the window."""
        cluster = self.cluster
        if cluster is None:
            return None
        from ..protocol.sdp import _norm
        peer = cluster.dvr_peers.get(_norm(path)) \
            or self._dvr_meta_peers.get(_norm(path))
        if peer is None:
            return None
        host, port, spans = peer
        span = spans.get(str(track_id))
        if span is not None and not span[0] <= int(win) <= span[1]:
            return None                 # advertised range excludes it
        key = (_norm(path), int(track_id), int(win))
        fut = self._dvr_fetches.get(key)
        if fut is None:
            if len(self._dvr_fetches) >= self._DVR_FETCH_INFLIGHT_MAX:
                # reap done-but-unclaimed entries first: a session torn
                # down mid-fetch never re-polls its key, and abandoned
                # results must not pin the cap shut forever
                for k in [k for k, f in self._dvr_fetches.items()
                          if f.done()]:
                    del self._dvr_fetches[k]
                if len(self._dvr_fetches) >= self._DVR_FETCH_INFLIGHT_MAX:
                    return None
            self._dvr_fetches[key] = self._ensure_dvr_fetch_pool().submit(
                self._dvr_fetch_blocking, host, int(port), path,
                int(track_id), int(win))
            return b""
        if not fut.done():
            return b""
        del self._dvr_fetches[key]
        try:
            return fut.result()
        except Exception:
            return None

    def _ensure_dvr_fetch_pool(self):
        if self._dvr_fetch_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._dvr_fetch_pool = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="dvr-fetch")
        return self._dvr_fetch_pool

    def _peer_http_get(self, host: str, port: int,
                       target: str) -> bytes | None:
        """One peer REST GET — helper-thread only.  Sends this node's
        REST credentials: on an auth-enabled cluster the peer's DVR
        endpoints sit behind the same shared config.  None on any
        non-200 / network failure."""
        import base64
        import http.client
        headers = {}
        if self.config.auth_enabled:
            cred = (f"{self.config.rest_username}:"
                    f"{self.config.rest_password}").encode()
            headers["Authorization"] = \
                "Basic " + base64.b64encode(cred).decode()
        try:
            conn = http.client.HTTPConnection(host, port, timeout=2.0)
            try:
                conn.request("GET", target, headers=headers)
                resp = conn.getresponse()
                if resp.status != 200:
                    return None
                return resp.read()
            finally:
                conn.close()
        except OSError:
            return None

    def _dvr_fetch_blocking(self, host: str, port: int, path: str,
                            track_id: int, win: int) -> bytes | None:
        from urllib.parse import quote
        return self._peer_http_get(
            host, port, f"/api/v1/dvrwindow?path={quote(path)}"
                        f"&track={track_id}&win={win}")

    async def _dvr_meta_sync(self, path: str) -> bool:
        """Bootstrap a fully-remote ``.dvr`` asset (ISSUE 13 satellite,
        closing the PR 12 open item): ask each live peer's REST
        ``/api/v1/dvrmeta`` for the asset's meta + per-track index
        documents, materialize them locally (index records + EMPTY spill
        file, so every window read degrades to the peer fetcher), and
        remember which peer answered so ``_dvr_peer_fetch`` can route
        window fills there even without an armed-asset advertisement."""
        cluster, dvr = self.cluster, self.dvr
        if cluster is None or dvr is None:
            return False
        from ..protocol.sdp import _norm
        # negative cache: a path no peer knew stays a miss for a while —
        # one cheap scanning client must not turn every repeat DESCRIBE
        # into a fresh cluster-wide HTTP sweep
        now = time.monotonic()
        until = self._dvr_meta_misses.get(_norm(path))
        if until is not None:
            if now < until:
                return False
            del self._dvr_meta_misses[_norm(path)]
        nodes = dict(cluster.last_nodes)
        if not nodes:
            try:
                nodes = await cluster.placement.live_nodes()
            except Exception:
                return False
        loop = asyncio.get_running_loop()
        for node, meta in nodes.items():
            if node == cluster.config.node_id:
                continue
            host, port = meta.get("ip"), meta.get("http")
            if not host or not port:
                continue
            doc = await loop.run_in_executor(
                self._ensure_dvr_fetch_pool(), self._dvr_meta_blocking,
                str(host), int(port), path)
            if not doc or not dvr.materialize(path, doc):
                continue
            spans = {}
            for tid, idx in (doc.get("tracks") or {}).items():
                wins = [int(r["win"]) for r in idx.get("windows", ())
                        if isinstance(r, dict) and "win" in r]
                if wins:
                    spans[str(tid)] = [min(wins), max(wins)]
            self._dvr_meta_peers[_norm(path)] = (str(host), int(port),
                                                 spans)
            return True
        if len(self._dvr_meta_misses) >= 512:     # bound scanner abuse
            self._dvr_meta_misses.clear()
        self._dvr_meta_misses[_norm(path)] = now + self._DVR_META_MISS_SEC
        return False

    def _dvr_meta_blocking(self, host: str, port: int,
                           path: str) -> dict | None:
        """HTTP GET of a peer's /api/v1/dvrmeta — helper-thread only."""
        import json
        from urllib.parse import quote
        raw = self._peer_http_get(
            host, port, f"/api/v1/dvrmeta?path={quote(path)}")
        if raw is None:
            return None
        try:
            doc = json.loads(raw.decode("utf-8", "replace"))
        except ValueError:
            return None
        return doc if isinstance(doc, dict) else None

    # -- erasure storage plumbing (ISSUE 20) -------------------------------
    #: in-flight restore cap — same bound and reasoning as the DVR
    #: peer-fill cap above
    _STORAGE_RESTORE_INFLIGHT_MAX = 32

    def _storage_on_finalize(self, result: dict) -> None:
        """DvrManager finalize hook: shard the finished asset on a
        storage worker thread (parity matmuls + peer pushes are
        blocking; finalize runs on the event loop)."""
        if self.storage is not None and self.dvr is not None:
            self.storage.store_async(result["path"], self.dvr)

    def _storage_restore(self, path: str, track_id: int,
                         win: int) -> bytes | None:
        """The spill chain's last resort, INLINE ON THE PUMP: kick the
        blocking shard-gather + GF reconstruct onto a storage worker and
        speak the fetch-pending protocol — ``b""`` while the future
        runs (the time-shift cursor HOLDS), the reconstructed blob when
        it lands, ``None`` when the stripe is beyond the parity budget."""
        st = self.storage
        if st is None:
            return None
        from ..protocol.sdp import _norm
        key = (_norm(path), int(track_id), int(win))
        fut = self._storage_fetches.get(key)
        if fut is None:
            if len(self._storage_fetches) >= \
                    self._STORAGE_RESTORE_INFLIGHT_MAX:
                for k in [k for k, f in self._storage_fetches.items()
                          if f.done()]:
                    del self._storage_fetches[k]
                if len(self._storage_fetches) >= \
                        self._STORAGE_RESTORE_INFLIGHT_MAX:
                    return None
            self._storage_fetches[key] = st.restore_async(
                path, int(track_id), int(win))
            return b""
        if not fut.done():
            return b""
        del self._storage_fetches[key]
        try:
            return fut.result()
        except Exception:
            return None

    def _peer_http_post(self, host: str, port: int, target: str,
                        body: bytes) -> bool:
        """One peer REST POST — helper-thread only, same auth rules as
        :meth:`_peer_http_get`."""
        import base64
        import http.client
        headers = {"Content-Type": "application/octet-stream"}
        if self.config.auth_enabled:
            cred = (f"{self.config.rest_username}:"
                    f"{self.config.rest_password}").encode()
            headers["Authorization"] = \
                "Basic " + base64.b64encode(cred).decode()
        try:
            conn = http.client.HTTPConnection(host, port, timeout=2.0)
            try:
                conn.request("POST", target, body=body, headers=headers)
                return conn.getresponse().status == 200
            finally:
                conn.close()
        except OSError:
            return False

    def _storage_push_blocking(self, node_meta: dict, asset: str,
                               name: str, payload: bytes,
                               manifest_json: str) -> bool:
        from urllib.parse import quote
        host, port = node_meta.get("ip"), node_meta.get("http")
        if not host or not port:
            return False
        return self._peer_http_post(
            str(host), int(port),
            f"/api/v1/shardpush?path={quote(asset)}&name={quote(name)}",
            manifest_json.encode() + b"\n\n" + payload)

    def _storage_fetch_blocking(self, node_meta: dict, asset: str,
                                name: str) -> bytes | None:
        from urllib.parse import quote
        host, port = node_meta.get("ip"), node_meta.get("http")
        if not host or not port:
            return None
        return self._peer_http_get(
            str(host), int(port),
            f"/api/v1/shard?path={quote(asset)}&name={quote(name)}")

    def _storage_manifest_blocking(self, node_meta: dict,
                                   asset: str) -> dict | None:
        import json
        from urllib.parse import quote
        host, port = node_meta.get("ip"), node_meta.get("http")
        if not host or not port:
            return None
        raw = self._peer_http_get(
            str(host), int(port),
            f"/api/v1/shardmeta?path={quote(asset)}")
        if raw is None:
            return None
        try:
            doc = json.loads(raw.decode("utf-8", "replace"))
        except ValueError:
            return None
        return doc if isinstance(doc, dict) else None

    def _write_vod_cache_meta(self) -> None:
        """Atomic write of the segment cache's hot-set metadata next to
        the relay checkpoint (same cadence, same tmp+rename rule)."""
        import json
        import os
        path = getattr(self, "_vod_ckpt_path", None)
        if path is None:
            return
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(self.vod_cache.snapshot(), fh,
                          separators=(",", ":"))
            os.replace(tmp, path)
        except OSError:
            pass

    def _park_tcp_record(self, path: str, track_id, rec: dict) -> None:
        """Checkpoint restore sink for ``kind=tcp`` records: park until
        the player re-attaches.  Records with no session id can never
        be matched — counted orphan immediately instead of rotting."""
        from .. import obs
        sid = rec.get("session_id")
        if not sid:
            obs.RESILIENCE_CKPT_TCP_ORPHANS.inc()
            obs.EVENTS.emit("ckpt.tcp_orphan", stream=path or "?",
                            reason="no_session_id")
            return
        self._pending_tcp[(path, track_id, sid)] = (rec, time.monotonic())

    def claim_tcp_restore(self, path: str, track_id, sid: str):
        """The rtsp SETUP re-attach hook: pop-and-return the parked
        record for (path, track, old Session id), or None."""
        ent = self._pending_tcp.pop((path, track_id, sid), None)
        return ent[0] if ent is not None else None

    def _sweep_pending_tcp(self) -> None:
        """Discard parked TCP records no player reclaimed within the
        RTSP timeout — stale-connection records must not adopt into a
        much later, unrelated subscriber."""
        if not self._pending_tcp:
            return
        from .. import obs
        now = time.monotonic()
        for key in [k for k, (_r, t0) in self._pending_tcp.items()
                    if now - t0 > self.config.rtsp_timeout_sec]:
            del self._pending_tcp[key]
            obs.RESILIENCE_CKPT_TCP_ORPHANS.inc()
            obs.EVENTS.emit("ckpt.tcp_orphan", stream=key[0],
                            reason="timeout", track=key[1])

    def _sweep_restored(self) -> None:
        """Reap restored subscribers whose player never proved itself:
        no ownership-proven RTCP for ``rtsp_timeout_sec`` (the same
        clock a live UDP player's connection is held to) removes the
        output — a vanished player cannot be relayed to forever."""
        self._sweep_pending_tcp()
        if not self._restored_subs:
            return
        now = time.monotonic()
        egress = self.rtsp.shared_egress
        for sub in list(self._restored_subs):
            stale = (now - sub.last_activity
                     > self.config.rtsp_timeout_sec)
            gone = self.registry.find(sub.path) is not sub.relay
            if not (stale or gone):
                continue
            self._restored_subs.remove(sub)
            if not gone:
                sub.stream.remove_output(sub.output)
            if egress is not None:
                egress.unregister(sub.output, sub)

    # ------------------------------------------------- egress backend probe
    def _init_egress_backend(self) -> None:
        """The boot-time probe ladder (ISSUE 8): resolve the configured
        ``egress_backend`` against what this kernel actually grants.

        Every probe failure — ENOSYS (pre-5.1), seccomp EPERM,
        RLIMIT_MEMLOCK too small for the registered arena — lands on the
        GSO rung with ONE structured ``egress.backend_fallback`` event
        and a fallback counter tick, never a counted hard_error (the
        same fix shape as the PR 4 GSO EINVAL probe)."""
        from .. import native, obs
        choice = self.config.egress_backend_choice()  # raises on a typo
        # engines must see the SAME normalized choice the ladder used —
        # handing them the raw pref ("Auto", "IO_URING ") would make
        # metrics claim one rung while every pass serves another
        self._egress_backend_choice = choice
        egress = self.rtsp.shared_egress
        effective = "scalar" if choice == "scalar" else "gso"
        if (choice in ("auto", "io_uring") and egress is not None
                and egress.active and native.available()):
            caps = native.uring_probe()
            if caps >= 0:
                try:
                    from ..relay.ring import SLOT_SIZE
                    self.uring_egress = native.UringEgress(
                        egress.fileno(), max_pkt=SLOT_SIZE)
                    effective = "io_uring"
                    self.uring_ingest_enabled = bool(
                        self.config.native_ingest
                        and caps & native.URING_CAP_RECV_MULTI)
                    self.rtsp.uring_ingest_enabled = \
                        self.uring_ingest_enabled
                except OSError as e:
                    caps = -(e.errno or 38)
            if caps < 0:
                import errno as errno_mod
                reason = errno_mod.errorcode.get(-caps, str(-caps))
                obs.EGRESS_BACKEND_FALLBACKS.inc(backend="io_uring")
                obs.EVENTS.emit(
                    "egress.backend_fallback",
                    level="warn" if choice == "io_uring" else "info",
                    backend="io_uring", fallback="gso", reason=reason)
                if self.error_log:
                    self.error_log.info(
                        f"egress backend: io_uring unavailable "
                        f"({reason}), serving from the GSO rung")
        self.egress_backend_effective = effective
        # info-style gauge: exactly one backend child reads 1 so a
        # forced-backend soak can assert what serves the wire
        for b in ("io_uring", "gso", "scalar"):
            obs.EGRESS_BACKEND_INFO.set(1 if b == effective else 0,
                                        backend=b)
        if self.error_log and effective != "gso":
            self.error_log.info(f"egress backend: {effective}"
                                + (f" (caps={self.uring_egress.caps})"
                                   if self.uring_egress else ""))

    # ---------------------------------------------------------- pump loop
    def _new_engine(self) -> TpuFanoutEngine:
        """The pump builds one per stream, on its first device wake:
        ``start()`` has settled the egress pair, ring and backend."""
        egress = self.rtsp.shared_egress
        return TpuFanoutEngine(
            egress_fd=egress.fileno() if egress is not None else None,
            uring=self.uring_egress,
            egress_backend=self._egress_backend_choice)

    def _on_device(self, stream) -> bool:
        """Whether the device path serves ``stream``: the engine tier is
        on and the stream has ``tpu_min_outputs`` outputs (a deployment
        of thin streams states 1; under it the scalar loop serves)."""
        return (self.config.tpu_fanout
                and stream.num_outputs >= self.config.tpu_min_outputs)

    def _wake_open(self, wake_ns: int | None) -> None:
        """First line of a wake: number it, open ``pump.wake`` (every
        span opened until ``_wake_close`` carries the number) and file
        the wake→pass queueing delay from the same clock read."""
        if self._wake_open_rec is not None:
            self._wake_close()          # a direct caller's previous wake
        self._wake_seq += 1
        TRACER.wake = self._wake_seq
        span = TRACER.open("pump.wake", "pump")
        t0 = t0_of(span)
        w2p_us = 0
        if wake_ns is not None:
            # wake→pass queueing delay: ingest set the event at wake_ns,
            # the loop got scheduled and reached the pass now — event-loop
            # lag the per-pass phases cannot see but players feel
            PROFILER.observe("wake_to_pass", "pump", t0 - wake_ns)
            w2p_us = (t0 - wake_ns) // 1000
        self._wake_open_rec = (span, t0, w2p_us)

    def _wake_close(self) -> None:
        """Last line of a wake: close the ledger's record and
        ``pump.wake``; the span's two clock reads are also the wake's
        sample in ``pump_wake_seconds`` and its share of
        ``pump_loop_seconds_total``."""
        if self._wake_open_rec is None:
            return
        span, t0, w2p_us = self._wake_open_rec
        self._wake_open_rec = None
        obs.LEDGER.end_wake()
        mb = self.pump.megabatch
        jobs, send_ns, hidden_ns = self.pump.jobs
        end = TRACER.close(span, streams=self.pump.streams,
                           stepped=len(self.pump.stepped),
                           handed=mb.handed if mb else 0,
                           walked=mb.walked if mb else 0,
                           sent=self.pump.sent, jobs=jobs,
                           send_us=send_ns // 1000,
                           hidden_us=hidden_ns // 1000,
                           wake_to_pass_us=w2p_us)
        TRACER.wake = None
        obs.PUMP_WAKE_SECONDS.observe((end - t0) / 1e9)
        obs.PUMP_LOOP_SECONDS.inc((end - t0) / 1e9, state="wake")

    def _reflect_all(self) -> int:
        wake_ns, self._wake_ns = self._wake_ns, None
        self._wake_open(wake_ns)
        LEDGER = obs.LEDGER             # (tests put a private one there)
        t = now_ms()
        # wake ledger (ISSUE 16): one record per wake, every unit below
        # tagged with its work class.  The record stays open through the
        # 1 Hz maintenance block in _pump_loop (end_wake there); direct
        # callers (tests, bench) are covered by begin_wake folding any
        # unclosed predecessor.
        LEDGER.begin_wake(wake_ns)
        # VOD group pacer (ISSUE 10): fill every hot session's rings up
        # to the lookahead horizon and collect its (stream, engine)
        # pairs — paced VOD subscribers are first-class relay streams
        # the pump steps and the megabatch scheduler coalesces with live
        # streams.  Any pacer failure degrades THIS wake's VOD service,
        # never the pump.
        vod_pairs = []
        if self.vod_pacer is not None and self.vod_pacer.sessions:
            _u = LEDGER.unit_start("vod_fill")
            try:
                vod_pairs = self.vod_pacer.tick(t)
            except Exception as e:
                vod_pairs = []
                if self.error_log:
                    self.error_log.warning(f"vod pacer: {e!r}")
            LEDGER.unit_end(_u, items=max(len(vod_pairs), 1))
        # DVR window spill (ISSUE 12): snapshot any live ring window the
        # head completed since the last wake (an integer compare per
        # armed stream when nothing did).  Runs BEFORE the reflect pass
        # so a time-shift cursor parked at the spill/ring seam sees the
        # freshest cold tail.  Failures degrade recording, not relaying.
        if self.dvr is not None and self.dvr._armed:
            _u = LEDGER.unit_start("dvr_spill")
            try:
                self.dvr.tick(t)
            except Exception as e:
                if self.error_log:
                    self.error_log.warning(f"dvr spill: {e!r}")
            LEDGER.unit_end(_u)
        return self.pump.wake(self.registry.sessions, vod_pairs, t)

    def _make_pump_wheel(self):
        """1 ms native timer wheel pacing the pump below the fixed tick
        (``csrc ed_wheel``; the reference's scheduler has a 10 ms floor,
        ``Task.cpp:334-335``).  ``Pump.arm`` posts each stepped stream's
        earliest bucket-delay release / reliable-UDP RTO / SR here; the
        pump sleeps until the wheel's next deadline instead of a full
        reflect interval, and the wake readies the streams whose timers
        ran out."""
        from .. import native
        if not native.available():
            return None
        try:
            return native.TimerWheel(now_ms())
        except RuntimeError:
            return None

    #: the most rounds ``_drain_readers`` yields before a wake: a pusher
    #: that never pauses (a backlog, a REST storm) holds the pump out for
    #: eight loop iterations and no longer
    _DRAIN_ROUNDS_MAX = 4

    async def _drain_readers(self) -> tuple[int, int]:
        """Let the readers run dry before a wake starts; returns (rounds
        yielded, packets the RTSP pushers pushed meanwhile).

        The loop's ready queue is FIFO and a stream read takes two
        iterations (the transport's read, then the connection task it
        resumed), so a pump that went from its wait straight into a
        blocking wake ran a whole wake in front of each stage of every
        batch ``select`` found (ARCHITECTURE.md "Why the pump yields
        before it works"; ``tests/test_pump_drain.py`` holds the order).
        One round = clear the event and yield for two iterations; a
        round in which ingest arrived is followed by another, up to
        ``_DRAIN_ROUNDS_MAX``.  ``_wake_ns`` keeps the first ingest's
        instant, so ``wake_to_pass`` includes the drain, and
        ``_reflect_all`` reads its clock after it: everything the drain
        pushed is due in this wake."""
        stats = self.rtsp.stats
        before = stats["packets_in"]
        rounds = 0
        while rounds < self._DRAIN_ROUNDS_MAX:
            self._pump_event.clear()
            rounds += 1
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            if not self._pump_event.is_set():
                break
        self._pump_event.clear()
        return rounds, stats["packets_in"] - before

    async def _pump_loop(self) -> None:
        interval = self.config.reflect_interval_ms / 1000.0
        last_prune = 0.0
        wheel = self.pump.wheel = self._make_pump_wheel()
        while self._running:
            timeout = interval
            if wheel is not None and wheel.pending:
                nd = wheel.next_deadline(now_ms())
                if nd >= 0:
                    timeout = min(interval, max(nd, 1) / 1000.0)
            # pump.sleep: what ended it is the wake's cause — ingest (a
            # pusher set the event), timer (a wheel deadline shortened
            # the wait and ran out) or interval (the full tick ran out)
            span = TRACER.open("pump.sleep", "pump",
                               timeout_ms=round(timeout * 1e3, 3))
            t_sleep = t0_of(span)
            try:
                await asyncio.wait_for(self._pump_event.wait(), timeout)
                cause = "ingest"
            except asyncio.TimeoutError:
                cause = "timer" if timeout < interval else "interval"
            # the drain is the pump waiting while the thread serves
            # ingest, which is what pump.sleep means: it closes after it
            rounds, packets = await self._drain_readers()
            t_woke = TRACER.close(span, cause=cause, drain_rounds=rounds,
                                  drain_packets=packets)
            obs.PUMP_LOOP_SECONDS.inc((t_woke - t_sleep) / 1e9,
                                      state="sleep")
            obs.PUMP_WAKES.inc(cause=cause)
            obs.PUMP_DRAIN_ROUNDS.inc(rounds)
            obs.PUMP_DRAIN_PACKETS.inc(packets)
            self._reflect_all()
            if self.config.slo_enabled:
                # a wake that compiled (and the one after) stays out of
                # the latency objective — SloWatchdog.note_wake
                self.slo.note_wake()
            if wheel is not None:
                # the wake advanced the wheel to its own clock sample
                # before it picked its streams; the timers of those it
                # stepped are armed against the same sample
                tok = TRACER.open("pump.deadlines", "pump")
                self.pump.arm(self.registry.sessions)
                TRACER.close(tok, streams=len(self.pump.stepped))
            now = time.monotonic()
            if now - last_prune >= 1.0:
                last_prune = now
                maint = TRACER.open("pump.maintenance", "pump")
                # first, while the skipped streams are as the wake left
                # them: the marks against the rule
                self.pump.audit()
                t = now_ms()
                for sess in list(self.registry.sessions.values()):
                    sess.prune(t)
                    for st in sess.streams.values():
                        st.send_upstream_rr(t)  # 5 s pusher liveness RRs
                if self.config.slo_enabled:
                    try:
                        self.slo.tick()
                    except Exception as e:
                        if self.error_log:
                            self.error_log.warning(f"slo tick: {e!r}")
                try:
                    # per-stream end-to-end freshness (ISSUE 15): one
                    # observation per actively-relaying stream per
                    # second, hop count from the freshness chain
                    from ..obs import fleet as fleet_mod
                    fleet_mod.observe_freshness(self)
                except Exception as e:
                    if self.error_log:
                        self.error_log.warning(f"freshness: {e!r}")
                try:
                    # audience observatory (ISSUE 18): derive stalls /
                    # QoE / storm latches from the columnar store —
                    # array passes per stream block, never per packet
                    from ..obs import AUDIENCE
                    AUDIENCE.tick()
                except Exception as e:
                    if self.error_log:
                        self.error_log.warning(f"audience tick: {e!r}")
                if self.ladder is not None:
                    try:
                        self._ladder_maintenance()
                    except Exception as e:
                        if self.error_log:
                            self.error_log.warning(f"ladder tick: {e!r}")
                if self.checkpoint is not None:
                    _u = obs.LEDGER.unit_start("checkpoint")
                    try:
                        wrote = self.checkpoint.maybe_write(self.registry)
                        if wrote and self.vod_cache is not None:
                            self._write_vod_cache_meta()
                    except Exception as e:
                        if self.error_log:
                            self.error_log.warning(f"checkpoint: {e!r}")
                    obs.LEDGER.unit_end(_u)
                if self.presence is not None:
                    self.presence.set_load(sum(
                        s.num_outputs
                        for s in self.registry.sessions.values()))
                    try:
                        await self.presence.sync_streams(self.registry.paths())
                    except Exception:
                        pass
                TRACER.close(maint)
            # close this wake's ledger record (and pump.wake) AFTER the
            # maintenance block: the 1 Hz duties ran on the same wake's
            # thread time, so their service belongs to the record a
            # queued packet's wait decomposes against
            self._wake_close()

    def _ladder_maintenance(self) -> None:
        """1 Hz ladder duties: evaluate recovery/SLO pressure, then shed
        the newest subscriber of any rung-3 stream (one per session per
        tick — shedding is a pressure valve, not an eviction sweep)."""
        from .. import obs
        from ..resilience import LEVEL_SHED
        stalls = {
            sess.path: sum(st.stats.stalls
                           for st in sess.streams.values())
            for sess in self.registry.sessions.values()}
        slo_status = None
        offender = None
        if self.config.slo_enabled:
            slo_status = self.slo.status()
            from ..obs import PROFILER
            offender = PROFILER.top_offender()
        self.ladder.tick(stalls, slo_status=slo_status, offender=offender)
        for sess in list(self.registry.sessions.values()):
            if self.ladder.level(sess.path) < LEVEL_SHED:
                continue
            for stream in sess.streams.values():
                out = self.ladder.shed_candidate(stream)
                if out is not None and stream.remove_output(out):
                    obs.RESILIENCE_SHED_OUTPUTS.inc()
                    obs.EVENTS.emit(
                        "ladder.shed", level="warn", stream=sess.path,
                        trace_id=sess.trace_id,
                        outputs=stream.num_outputs)
                    break

    async def _status_loop(self) -> None:
        """The 1 Hz supervisor's status duties (RunServer.cpp:620-719):
        console columns every ``stats_interval_sec``, status file every
        ``status_file_interval_sec``."""
        import sys
        last_file = 0.0
        # tick fast enough for BOTH outputs: -S 60 must not stretch a 10 s
        # file cadence to 60 s
        enabled = [i for i in (self.config.stats_interval_sec,
                               self.config.status_file_interval_sec
                               if self.config.status_file_path else 0) if i]
        interval = min(enabled) if enabled else 1
        last_console = 0.0
        while self._running:
            await asyncio.sleep(interval)
            snap = self.status.tick()       # the ONE baseline advance per
            # tick; console and file read the returned snapshot (and any
            # concurrent REST reader uses the pure snapshot())
            now = time.monotonic()
            if (self.config.stats_interval_sec and now - last_console
                    >= self.config.stats_interval_sec - interval / 2):
                last_console = now
                if self.status.needs_header():
                    print(self.status.header_line(), file=sys.stderr)
                print(self.status.console_line(snap), file=sys.stderr,
                      flush=True)
            if (self.config.status_file_path
                    and now - last_file
                    >= self.config.status_file_interval_sec - interval / 2):
                last_file = now
                try:
                    self.status.write_file(self.config.status_file_path,
                                           snap)
                except OSError:
                    pass

    async def _sweep_loop(self) -> None:
        while self._running:
            await asyncio.sleep(self.config.timeout_sweep_sec)
            self.rtsp.sweep_timeouts()
            self._sweep_restored()
            self.relay_source.sweep()
            self.transcodes.sweep()
            self.hls.sweep()
            await self.pulls.sweep()
            # background scrub (ISSUE 20): a bounded batch of local
            # shard crc32 / parity-oracle verifications per interval,
            # off the event loop — corruption is found BEFORE a reader
            # needs the shard
            if self.storage is not None:
                now = time.monotonic()
                if now >= self._storage_scrub_due:
                    self._storage_scrub_due = (
                        now + self.config.storage_scrub_interval_sec)
                    st = self.storage
                    st._executor().submit(st.scrub_tick)

    async def _rtsp_port_http_get(self, conn, target: str,
                                  headers: dict) -> bool:
        """Plain HTTP GET on the RTSP port: icy MP3 streams + stats page."""
        path = target.split("?")[0]
        if path.lower().endswith(".mp3"):
            await self.mp3.stream(conn.writer, path, headers)
            return True
        if path.lower().endswith(".m3u"):
            # directory scan + per-file ID3 probes are blocking IO —
            # keep them off the shared event loop
            pl = await asyncio.to_thread(self.mp3.playlist, path)
            if pl is not None:
                body = pl.encode()
                conn.writer.write(
                    b"HTTP/1.0 200 OK\r\n"
                    b"Content-Type: audio/x-mpegurl\r\n"
                    b"Content-Length: " + str(len(body)).encode()
                    + b"\r\n\r\n" + body)
                return True
        if path in ("/", "/stats"):
            html = self.rest._webstats_html().encode()
            conn.writer.write(
                b"HTTP/1.0 200 OK\r\nContent-Type: text/html\r\n"
                b"Content-Length: " + str(len(html)).encode() + b"\r\n\r\n"
                + html)
            return True
        return False

    # ------------------------------------------------------------- queries
    def server_info(self) -> dict:
        # pure snapshot(): REST readers share the status loop's tick()
        # baseline instead of racing it (the old sample()-everywhere
        # design zeroed whichever reader came second in a tick)
        d = self.status.snapshot()
        mesh_info = {}
        if self.pump.mesh is not None:
            # the mesh→process mapping, live (previously only the
            # multichip dryrun could see process_span)
            try:
                from ..parallel.distributed import mesh_summary
                mesh_info = mesh_summary(self.pump.mesh)
                if self.pump.megabatch is not None:
                    mesh_info["MeshShardedPasses"] = str(
                        self.pump.megabatch.sharded_passes)
            except Exception:
                mesh_info = {}
        return {
            **mesh_info,
            "ServerName": "easydarwin-tpu",
            "Version": "0.1.0",
            "UpTimeSec": str(d["uptime_sec"]),
            "RTSPPort": str(self.rtsp.port or self.config.rtsp_port),
            "ServicePort": str(self.rest.port or self.config.service_port),
            "Connections": str(d["rtsp_connections"]),
            "PushSessions": str(d["push_sessions"]),
            "Requests": str(d["requests"]),
            "PacketsIn": str(d["packets_in"]),
            "PacketsOut": str(d["packets_out"]),
            "InRatePps": str(d["in_rate"]),
            "OutRatePps": str(d["out_rate"]),
            "IngestToWireP99Ms": str(d["ingest_to_wire_p99_ms"]),
            "TpuFanout": "1" if self.config.tpu_fanout else "0",
            # what the engine actually runs on (device.resolve at boot;
            # empty strings = engine tier off, JAX never initialised)
            **self._device_keys(),
            # wake-ledger summary (ISSUE 16): the console's "is the pump
            # starving" answer without a /metrics scrape
            "LedgerTopWaitClass": str(d.get("ledger_top_wait_class", "")),
            "LedgerLastWakeMs": str(d.get("ledger_last_wake_ms", 0.0)),
        }

    def _device_keys(self) -> dict:
        from .. import native
        d = self.device_info or {"platform": "", "kind": "", "count": 0}
        nb = native.build_info() if native.loaded() else {}
        return {
            "Platform": d["platform"],
            "DeviceKind": d["kind"],
            "DeviceCount": str(d["count"]),
            "NativeCore": "1" if native.loaded() else "0",
            "NativeSourceDigest": nb.get("source_digest", ""),
            "NativeBuiltAtBoot":
                "1" if nb.get("built_this_process") else "0",
        }

    def live_sessions(self) -> list[dict]:
        out = []
        for sess in self.registry.sessions.values():
            st = sess.stats()
            out.append({
                "Path": sess.path,
                "Url": f"rtsp://{self.config.wan_ip}:"
                       f"{self.rtsp.port or self.config.rtsp_port}{sess.path}",
                "Outputs": str(sess.num_outputs),
                "AgeSec": str((now_ms() - sess.created_ms) // 1000),
                "Streams": st["streams"],
            })
        return out

    def device_stream_url(self, device: str) -> str | None:
        path = f"/{device.strip('/')}"
        for cand in (path, f"/live/{device.strip('/')}"):
            if self.registry.find(cand) is not None:
                return (f"rtsp://{self.config.wan_ip}:"
                        f"{self.rtsp.port or self.config.rtsp_port}{cand}")
        return None
