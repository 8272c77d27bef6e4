"""Per-process simulation node entry point.

Reference: simul/node/main.go:33-144 — connect the monitor sink, load config
+ registry CSV, build K Handel instances (one per -id), signal the START
barrier, run until threshold, record `sigen`/`net`/`sigs` measures, verify
the final signature against the registry, signal END.

Run as: python -m handel_tpu.sim.node --config C --registry R --master M
        --monitor MON --run I --ids 1,2,3

All logical nodes in this process share one asyncio loop, one UDP socket per
node, and (with --shared-verifier) one device batch-verifier launch queue.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

from handel_tpu.core.crypto import Constructor, verify_multisignature
from handel_tpu.core.handel import Handel
from handel_tpu.models.registry import is_device_scheme, new_scheme
from handel_tpu.network.chaos import ChaosNetwork
from handel_tpu.network.encoding import CounterEncoding
from handel_tpu.network.udp import UDPNetwork
from handel_tpu.network.tcp import TCPNetwork
from handel_tpu.network.quic import QUICNetwork
from handel_tpu.sim import keys as simkeys
from handel_tpu.sim.adversary import (
    adversary_roles,
    build_adversary,
    check_threshold_reachable,
)
from handel_tpu.core.trace import FlightRecorder
from handel_tpu.sim.allocator import new_allocator
from handel_tpu.sim.config import load_config
from handel_tpu.sim.monitor import CounterIO, HistogramIO, Sink, TimeMeasure
from handel_tpu.sim.sync import STATE_END, STATE_START, SyncSlave

MSG = b"handel-tpu simulation message"


async def run_node_process(args) -> int:
    cfg = load_config(args.config)
    run = cfg.runs[args.run]

    # live telemetry plane (core/metrics.py): the HTTP endpoint comes up
    # BEFORE the scheme builds, so /healthz answers during a long warmup
    # while /readyz stays 503 until the readiness probes pass — scheme
    # warmed, breaker not open, monitor sink connected. `metrics = false`
    # (or no --metrics-port from the platform) keeps the plane fully off:
    # zero threads, zero sockets.
    mreg = mserver = None
    ready_state = {"scheme_warmed": False, "service": None}
    if cfg.metrics and getattr(args, "metrics_port", -1) >= 0:
        from handel_tpu.core.metrics import MetricsRegistry, MetricsServer

        mreg = MetricsRegistry()
        mreg.add_readiness(
            "scheme_warmed", lambda: ready_state["scheme_warmed"]
        )
        mreg.add_readiness(
            "breaker_closed",
            lambda: (
                ready_state["service"] is None
                or ready_state["service"].breaker.state != "open"
            ),
        )
        mreg.add_readiness(
            "monitor_sink", lambda: bool(sink) or not args.monitor
        )
        sink = None  # readiness closes over it before the real bind below
        mserver = MetricsServer(mreg, port=args.metrics_port).start()
        # the BOUND port is authoritative (--metrics-port 0 = ephemeral):
        # drop it next to the config so scrapers can discover manual runs
        addr_path = os.path.join(
            os.path.dirname(os.path.abspath(args.config)),
            f"metrics_{args.ids.split(',')[0]}.addr",
        )
        try:
            with open(addr_path, "w") as f:
                f.write(mserver.address + "\n")
        except OSError:
            pass
        print(f"metrics: serving on http://{mserver.address}", flush=True)

    if is_device_scheme(cfg.scheme):
        # select the JAX platform BEFORE the scheme module imports jax;
        # fake/host schemes never touch jax at all. mesh_devices > 1 on a
        # chip-less host needs that many virtual CPU devices. This process
        # is the chip's one owner (the platform parent stays off jax).
        from handel_tpu.utils.jaxenv import (
            apply_platform_env,
            enable_compile_cache,
        )

        apply_platform_env(
            force_host_device_count=(
                cfg.mesh_devices if cfg.mesh_devices > 1 else None
            )
        )
        enable_compile_cache()
    scheme = new_scheme(
        cfg.scheme,
        **(
            {
                "batch_size": cfg.batch_size,
                "mesh_devices": cfg.mesh_devices,
                "fp_backend": cfg.fp_backend,
                # residency only means something on the rns backend; None
                # lets the pairing layer auto-detect (and avoids the
                # explicit-True-on-cios error)
                "rns_resident": (
                    cfg.rns_resident if cfg.fp_backend == "rns" else None
                ),
            }
            if is_device_scheme(cfg.scheme)
            else {}
        ),
    )
    ids = [int(x) for x in args.ids.split(",") if x != ""]
    threshold = run.resolved_threshold()
    # scheme construction runs the device warmup (models/bn254_jax.py
    # warms its kernels at build); fake/host schemes are warm by definition
    ready_state["scheme_warmed"] = True

    # span flight recorder (core/trace.py): one ring per process, every
    # logical node recording under its id as the Chrome-trace tid; dumped
    # as trace_<first-id>.json into --trace-dir after the END barrier
    recorder = None
    if getattr(args, "trace_dir", ""):
        recorder = FlightRecorder(capacity=cfg.trace_capacity, pid=os.getpid())

    sink = Sink(args.monitor) if args.monitor else None
    # process-wide batch-plane telemetry (SURVEY.md §5.1): G2 subgroup-check
    # cost (which starts accruing at registry load, right below), shared
    # launch fill ratio and device wall time added once the service exists.
    # Snapshot BEFORE the registry unmarshals so startup cost is attributed.
    plane = device_meas = None
    if sink:
        from handel_tpu.core.report import SUBGROUP_CHECKS, ReportAggregator

        plane = ReportAggregator(subgroup=SUBGROUP_CHECKS)
        device_meas = CounterIO(sink, "device", plane)

    records = simkeys.read_registry_csv(args.registry)
    registry = simkeys.registry_from_records(records, scheme)

    # WAN scenario plane (sim/config.py ScenarioParams): geo placement,
    # stake weights, weighted threshold — all derived identically in every
    # process from the shared TOML
    scen = cfg.scenario
    geo_base = scen.geo_config() if scen.geo_enabled() else None
    weights = scen.make_weights(run.nodes) if scen.weights_enabled() else None
    weight_threshold = (
        scen.weight_threshold(threshold, run.nodes, weights)
        if weights is not None
        else 0.0
    )

    # byzantine roles (sim/adversary.py): recompute the allocator's offline
    # set locally so every process derives the SAME id -> role mapping
    roles: dict[int, str] = {}
    if run.adversaries.total():
        alloc = new_allocator(cfg.allocator).allocate(
            run.nodes, 1, run.processes, run.failing
        )
        offline = {nid for nid, slot in alloc.items() if not slot.active}
        roles = adversary_roles(run.adversaries.counts(), run.nodes, offline)
        check_threshold_reachable(
            threshold,
            run.nodes,
            run.failing,
            roles,
            weights=weights,
            weight_threshold=weight_threshold,
        )

    # one transport per logical node, bound to its registry address
    nets, handels = [], []
    shared_service = None
    rpc_client = None
    rpc_server = None
    if args.verifier and not cfg.baseline:
        # chip-less process: ship candidate batches to the fleet's device
        # host instead of preparing a local device (no kernels compiled
        # here at all — parallel/rpc_verifier.py)
        from handel_tpu.parallel.rpc_verifier import RPCVerifier

        rpc_client = RPCVerifier(args.verifier)
        if plane is not None:
            plane.add("rpc", rpc_client)
    elif (
        cfg.shared_verifier
        and hasattr(scheme.constructor, "Device")
        and not cfg.baseline
    ):
        from handel_tpu.core.report import KernelTimer
        from handel_tpu.parallel.batch_verifier import BatchVerifierService

        # prepare() builds the device for this scheme's curve family AND
        # caches it on the constructor, so per-node constructor.batch_verify
        # calls reuse the same registry upload + executables
        device = scheme.constructor.prepare(registry.public_keys())
        # kernel-time trace hook (SURVEY.md §5.1): every shared launch's
        # wall time lands on the monitor plane. The timer sits on fetch
        # (verdict-arrival latency) + dispatch (host prep/enqueue) because
        # the pipelined service calls those directly; device.batch_verify
        # routes through the same instance attributes, so direct calls are
        # timed too
        launch_timer = KernelTimer(device.fetch, name="launch")
        device.fetch = launch_timer
        dispatch_timer = KernelTimer(device.dispatch, name="dispatch")
        device.dispatch = dispatch_timer
        # host failover target for the verifier circuit breaker: the
        # scheme's inherited host-side serial batch_verify (aggregate the
        # registry pubkey objects + one reference pairing check per
        # candidate) — a dead device degrades throughput, not the run
        pubkeys = registry.public_keys()

        def host_fallback(msg, reqs, _c=scheme.constructor, _pk=pubkeys):
            return Constructor.batch_verify(_c, msg, _pk, reqs)

        shared_service = BatchVerifierService(
            device, fallback=host_fallback, recorder=recorder
        )
        ready_state["service"] = shared_service
        if plane is not None:
            plane.add("verifier", shared_service)
            plane.add("launch", launch_timer)
            plane.add("dispatch", dispatch_timer)
        if args.serve_verifier:
            # this is the fleet's device host: serve the batch plane to
            # every chip-less process BEFORE the START barrier, so remote
            # clients never race the bind
            from handel_tpu.parallel.rpc_verifier import VerifierServer

            rpc_server = VerifierServer(
                shared_service,
                scheme.constructor,
                port=args.serve_verifier,
            )
            await rpc_server.start()
            if plane is not None:
                plane.add("rpcserve", rpc_server)

    for nid in ids:
        rec = records[nid]
        enc = CounterEncoding()
        if cfg.network == "tcp":
            net = TCPNetwork(rec.address, encoding=enc)
        elif cfg.network == "quic":
            net = QUICNetwork(rec.address, encoding=enc)
        else:
            net = UDPNetwork(rec.address, encoding=enc)
        if geo_base is not None:
            # geo-latency planet model (network/geo.py): region-pair WAN
            # delay, chaos faults composed on top when any rate is set
            from handel_tpu.network.geo import GeoNetwork

            net = GeoNetwork(
                net,
                geo_base.for_node(nid),
                chaos=cfg.chaos.for_node(nid) if cfg.chaos.any() else None,
            )
        elif cfg.chaos.any():
            # fault-injection plane (network/chaos.py): same transport
            # underneath, seeded per-link faults on top
            net = ChaosNetwork(net, cfg.chaos.for_node(nid))
        await net.start()
        nets.append(net)
        sk = simkeys.secret_of(rec, scheme)
        if cfg.baseline:  # comparison protocols (simul/p2p shared binary)
            from handel_tpu.baselines.gossip import GossipAggregator
            from handel_tpu.baselines.gossipsub import GossipSubAggregator

            agg_cls, kw = (
                (GossipSubAggregator, {})
                if cfg.baseline == "gossipsub"
                else (
                    GossipAggregator,
                    # same recv/verify/merge spans as Handel, so baseline
                    # traces compare like-for-like in the trace CLI
                    {
                        "connector": "full",
                        "recorder": recorder,
                        "trace_tid": nid,
                    },
                )
            )
            h = agg_cls(
                net,
                registry,
                registry.identity(nid),
                scheme.constructor,
                MSG,
                sk.sign(MSG),
                threshold,
                **kw,
            )
        else:
            hconf = run.handel.to_config(threshold, seed=nid)
            hconf.batch_size = cfg.batch_size
            hconf.recorder = recorder
            if geo_base is not None:
                hconf.region = geo_base.region_of(nid)
            if weights is not None:
                hconf.weights = weights
                hconf.weight_threshold = weight_threshold
            if shared_service is not None:
                hconf.verifier = shared_service.verify
            elif rpc_client is not None:
                hconf.verifier = rpc_client.verify
            if nid in roles:
                h = build_adversary(
                    roles[nid],
                    net,
                    registry,
                    registry.identity(nid),
                    scheme.constructor,
                    MSG,
                    sk,
                    hconf,
                    flood_pps=run.adversaries.flood_pps,
                    leave_after_s=run.adversaries.churn_after_ms / 1000.0,
                )
            else:
                h = Handel(
                    net,
                    registry,
                    registry.identity(nid),
                    scheme.constructor,
                    MSG,
                    sk.sign(MSG),
                    hconf,
                )
        handels.append((nid, h, net))

    # churn: a departing node notifies its co-located survivors directly
    # (Handel.mark_departed -> re-level + threshold re-evaluation). Cross-
    # process survivors see the departure as silence, exactly like a
    # `failing` node — the callback is a process-local accelerant, not a
    # consensus channel.
    from handel_tpu.sim.adversary import ROLE_CHURNER

    churners = [h for _, h, _ in handels if getattr(h, "role", None) == ROLE_CHURNER]
    if churners:
        survivors = [h for _, h, _ in handels]

        def _on_depart(departed_id: int, _peers=survivors) -> None:
            for p in _peers:
                md = getattr(p, "mark_departed", None)
                if md is not None:
                    md(departed_id)

        for ch in churners:
            ch.on_depart = _on_depart

    # registry-backed scrape surfaces: every logical node's protocol (sigs),
    # transport (net) and peer-penalty planes under a node label, the
    # process-wide verifier under device_verifier, device/XLA state under
    # device, host crypto counters under host (naming: handel_<plane>_<key>)
    if mreg is not None:
        for nid, h, net in handels:
            lbl = {"node": str(nid)}
            if hasattr(h, "values"):
                mreg.register_values("sigs", h, labels=lbl)
            if hasattr(h, "histograms"):
                mreg.register_histograms("sigs", h, labels=lbl)
            if hasattr(net, "values"):
                mreg.register_values("net", net, labels=lbl)
            if hasattr(net, "histograms"):
                mreg.register_histograms("net", net, labels=lbl)
            scorer = getattr(h, "scorer", None)
            if scorer is not None:
                mreg.register_values("penalty", scorer, labels=lbl)
        if shared_service is not None:
            mreg.register_values("device_verifier", shared_service)
        if plane is not None:
            mreg.register_values("host", plane)
        if recorder is not None:
            mreg.register_values("trace", recorder)
        if is_device_scheme(cfg.scheme) and not cfg.baseline:
            from handel_tpu.parallel.telemetry import DeviceTelemetry

            telemetry = DeviceTelemetry(
                service=shared_service,
                trace_dir=getattr(args, "trace_dir", "")
                or os.path.dirname(os.path.abspath(args.config)),
            )
            mreg.register_values("device", telemetry)
            mserver.set_profiler(telemetry.profile)

    # barrier: ready to start (one slave per logical node id)
    slaves = []
    for nid, _, _ in handels:
        s = SyncSlave(args.master, nid)
        await s.start()
        slaves.append(s)
    await asyncio.gather(
        *(s.signal_and_wait(STATE_START, cfg.max_timeout_s) for s in slaves)
    )
    if recorder is not None and slaves:
        # best (min-RTT) offset-vs-master estimate from the START handshake
        # (sim/sync.py): carried in the trace export so merge_traces aligns
        # this process's timeline with the rest of the fleet
        best_slave = min(slaves, key=lambda s: s.clock_rtt)
        if best_slave.clock_rtt != float("inf"):
            recorder.clock_offset = best_slave.clock_offset

    measures = []
    for nid, h, net in handels:
        if sink:
            # Handel.values() now carries the whole per-node plane —
            # processing + store + penalty counters; gossip reports itself.
            # Histogram reporters additionally ship the latency
            # distributions behind the _p50/_p90/_p99 CSV columns.
            ms = [TimeMeasure(sink, "sigen"), CounterIO(sink, "net", net),
                  CounterIO(sink, "sigs", h)]
            if hasattr(h, "histograms"):
                ms.append(HistogramIO(sink, "sigs", h))
            if hasattr(net, "histograms"):
                # chaos/geo delay distribution -> net_delayMs_p50/_p90/_p99
                ms.append(HistogramIO(sink, "net", net))
            measures.append(tuple(ms))
        else:
            measures.append(None)
        h.start()

    async def one_done(h):
        if hasattr(h, "final_signatures"):  # Handel
            return await h.final_signatures.get()
        return await h.final  # gossip baseline

    # adversarial nodes never emit an honest final signature — only the
    # honest cohort gates run completion
    honest = [
        (nid, h, net)
        for nid, h, net in handels
        if getattr(h, "role", None) is None
    ]
    try:
        finals = await asyncio.wait_for(
            asyncio.gather(*(one_done(h) for _, h, _ in honest)),
            timeout=cfg.max_timeout_s,
        )
    except asyncio.TimeoutError:
        # stall diagnostics: per-node progress is the only evidence a
        # multi-process deadlock leaves behind
        for nid, h, net in handels:
            best = getattr(h, "store", None) and h.store.full_signature()
            card = best.cardinality() if best else 0
            vals = net.values() if hasattr(net, "values") else {}
            print(
                f"node {nid}: STALLED at {card}/{threshold} "
                f"(sent={vals.get('sentPackets')} rcvd={vals.get('rcvdPackets')} "
                f"dropped={vals.get('droppedPackets')})",
                file=sys.stderr,
            )
        raise

    ok = True
    finals_by_nid = dict(zip((nid for nid, _, _ in honest), finals))
    for (nid, h, net), m in zip(handels, measures):
        if m:
            for meas in m:
                meas.record()
        ms = finals_by_nid.get(nid)
        if ms is not None and not verify_multisignature(
            MSG, ms, registry, scheme.constructor
        ):
            print(f"node {nid}: FINAL SIGNATURE INVALID", file=sys.stderr)
            ok = False
        h.stop()
        net.stop()

    await asyncio.gather(
        *(s.signal_and_wait(STATE_END, cfg.max_timeout_s) for s in slaves)
    )
    # batch-plane record (once per process) AFTER the fleet-wide END
    # barrier: a verifier-serving process keeps answering other hosts'
    # RPC batches until every node everywhere is done, so recording at
    # local-node completion would freeze its served counters early. The
    # master's monitor stays up until it has collected process exits, so
    # this post-barrier record still lands.
    if device_meas is not None:
        device_meas.record()
    if recorder is not None:
        recorder.dump(
            os.path.join(args.trace_dir, f"trace_{ids[0] if ids else 0}.json")
        )
    if mserver is not None:
        # keep the endpoint up briefly so scrapers catch the final counter
        # state of a short run (`sim watch` sets this; default 0)
        if cfg.metrics_linger_s > 0:
            await asyncio.sleep(cfg.metrics_linger_s)
        mserver.stop()
    for s in slaves:
        s.stop()
    if rpc_client is not None:
        rpc_client.stop()
    if rpc_server is not None:
        rpc_server.stop()
    if sink:
        sink.close()
    if ok:
        print(f"node process finished OK ids={ids}")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--registry", required=True)
    ap.add_argument("--master", required=True)
    ap.add_argument("--monitor", default="")
    ap.add_argument("--run", type=int, default=0)
    ap.add_argument("--ids", required=True)
    # run-scoping marker only: never read, but present in argv so the
    # orchestrator's cleanup pkill can match THIS run's node processes
    # without killing other simulations on a shared host (sim/remote.py)
    ap.add_argument("--tag", default="")
    # batch-plane RPC (parallel/rpc_verifier.py): serve the local shared
    # verifier on this port / verify through the fleet's device host
    ap.add_argument("--serve-verifier", type=int, default=0)
    ap.add_argument("--verifier", default="")
    # span tracing: record a flight recorder (core/trace.py) and dump its
    # Chrome trace_event JSON into this directory at run end
    ap.add_argument("--trace-dir", default="")
    # live telemetry (core/metrics.py): serve /metrics+/healthz+/readyz on
    # this port (0 = ephemeral, bound port written next to the config);
    # absent (-1) or `metrics = false` in the TOML = plane fully off
    ap.add_argument("--metrics-port", type=int, default=-1)
    args = ap.parse_args()
    return asyncio.run(run_node_process(args))


if __name__ == "__main__":
    sys.exit(main())
