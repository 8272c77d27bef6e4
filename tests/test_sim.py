"""Simulation harness tests.

Tier-4 of the reference test strategy (SURVEY.md §4): TestMainLocalHost
(simul/main_test.go:17-60) spawns real processes over real sockets with the
sync barrier and the monitor, and asserts success + a results CSV. Plus unit
tests for allocator invariants (allocator_test.go:16), registry CSV
round-trip (parser_test.go:48), sync barrier (sync_test.go:8), and stats.
"""

import asyncio
import csv
import os

import pytest

from handel_tpu.sim.allocator import RoundRobin, RoundRandomOffline
from handel_tpu.sim.config import HandelParams, RunConfig, SimConfig, dump_config, load_config
from handel_tpu.sim.keys import (
    generate_nodes,
    read_registry_csv,
    registry_from_records,
    secret_of,
    write_registry_csv,
)
from handel_tpu.sim.monitor import Monitor, Sink, Stats
from handel_tpu.sim.platform import LocalhostPlatform, free_ports
from handel_tpu.sim.sync import STATE_START, SyncMaster, SyncSlave
from handel_tpu.models.fake import FakeScheme


def test_allocator_invariants():
    for alloc_cls in (RoundRobin, RoundRandomOffline):
        alloc = alloc_cls().allocate(40, 2, 4, failing=10)
        assert len(alloc) == 40
        assert sum(1 for s in alloc.values() if not s.active) == 10
        assert {s.process for s in alloc.values()} == set(range(8))


def test_registry_csv_roundtrip(tmp_path):
    scheme = FakeScheme()
    records = generate_nodes(scheme, [f"127.0.0.1:{4000+i}" for i in range(5)])
    path = str(tmp_path / "reg.csv")
    write_registry_csv(path, records)
    back = read_registry_csv(path)
    assert [(r.id, r.address) for r in back] == [
        (r.id, r.address) for r in records
    ]
    reg = registry_from_records(back, scheme)
    assert reg.size() == 5
    sk = secret_of(back[3], scheme)
    assert sk.id == 3


def test_sync_barrier():
    async def go():
        (port,) = [free_ports(1)[0]]
        master = SyncMaster(port, expected=3)
        await master.start()
        slaves = [SyncSlave(f"127.0.0.1:{port}", i) for i in range(3)]
        for s in slaves:
            await s.start()
        await asyncio.gather(
            master.wait_all(STATE_START, 10.0),
            *(s.signal_and_wait(STATE_START, 10.0) for s in slaves),
        )
        master.stop()
        for s in slaves:
            s.stop()

    asyncio.run(go())


def test_monitor_stats(tmp_path):
    async def go():
        (port,) = free_ports(1)
        mon = Monitor(port)
        await mon.start()
        sink = Sink(f"127.0.0.1:{port}")
        for v in (1.0, 3.0):
            sink.record("sigen", {"wall": v})
        await asyncio.sleep(0.2)
        mon.stop()
        sink.close()
        return mon.stats

    stats = asyncio.run(go())
    cols = stats.columns()
    assert "sigen_wall_avg" in cols
    row = dict(zip(cols, stats.row()))
    assert row["sigen_wall_avg"] == 2.0
    assert row["sigen_wall_min"] == 1.0 and row["sigen_wall_max"] == 3.0
    path = str(tmp_path / "stats.csv")
    stats.write_csv(path)
    assert os.path.exists(path)


def test_config_toml_roundtrip(tmp_path):
    from handel_tpu.sim.config import HostSpec

    cfg = SimConfig(
        scheme="fake",
        mesh_devices=4,
        master_ip="10.0.0.9",
        base_port=21000,
        hosts=[HostSpec(connect="ssh:u@h1", ip="10.0.0.2", python="python3")],
        runs=[RunConfig(nodes=12, threshold=7, failing=2, processes=3,
                        handel=HandelParams(period_ms=5.0))],
    )
    path = tmp_path / "sim.toml"
    path.write_text(dump_config(cfg))
    back = load_config(str(path))
    assert back.scheme == "fake"
    assert back.mesh_devices == 4
    assert back.master_ip == "10.0.0.9" and back.base_port == 21000
    assert back.hosts == cfg.hosts
    assert back.runs[0].nodes == 12
    assert back.runs[0].handel.period_ms == 5.0
    assert back.runs[0].resolved_threshold() == 7


@pytest.mark.parametrize("scheme,nodes,processes,failing", [
    ("fake", 8, 2, 0),
    ("fake", 16, 4, 3),
])
def test_localhost_platform(tmp_path, scheme, nodes, processes, failing):
    """TestMainLocalHost equivalent: real processes, UDP, barrier, monitor."""
    threshold = (nodes - failing) // 2 + 1
    cfg = SimConfig(
        network="udp",
        scheme=scheme,
        max_timeout_s=60.0,
        runs=[
            RunConfig(
                nodes=nodes,
                threshold=threshold,
                failing=failing,
                processes=processes,
            )
        ],
    )

    async def go():
        plat = LocalhostPlatform(cfg, str(tmp_path))
        return await plat.start_run(0)

    res = asyncio.run(go())
    if not res.ok:
        for out, err in res.outputs:
            print(out.decode(errors="replace"))
            print(err.decode(errors="replace"))
    assert res.ok
    assert os.path.exists(res.csv_path)
    with open(res.csv_path) as f:
        rows = list(csv.reader(f))
    header = rows[0]
    assert "sigen_wall_avg" in header
    assert any("net_sentBytes" in h for h in header)


def test_remote_platform_two_hosts(tmp_path):
    """The multi-host platform (sim/remote.py, the aws.go analog) with two
    localhost-as-remote hosts: the package is packed + shipped into each
    host's staging dir, node processes run FROM the shipped copies on
    separately-launched "hosts", and the orchestrator's barriers + monitor
    produce the same stats CSV as the localhost platform."""
    from handel_tpu.sim.config import HostSpec
    from handel_tpu.sim.platform import run_simulation

    cfg = SimConfig(
        network="udp",
        scheme="fake",
        max_timeout_s=60.0,
        hosts=[
            HostSpec(connect="local", workdir=str(tmp_path / "hostA")),
            HostSpec(connect="local", workdir=str(tmp_path / "hostB")),
        ],
        runs=[RunConfig(nodes=8, threshold=5, processes=1)],
    )
    results = asyncio.run(
        run_simulation(cfg, str(tmp_path / "out"), platform="remote")
    )
    res = results[0]
    if not res.ok:
        for out, err in res.outputs:
            print(out.decode(errors="replace"))
            print(err.decode(errors="replace"))
    assert res.ok
    # deployment really happened: both hosts got the package + run files
    for host in ("hostA", "hostB"):
        assert (tmp_path / host / "handel_tpu" / "sim" / "node.py").exists()
        assert (tmp_path / host / "registry_0.csv").exists()
    # two hosts -> two node processes (one per host), each with 4 nodes
    assert len(res.outputs) == 2
    with open(res.csv_path) as f:
        header = list(csv.reader(f))[0]
    assert "sigen_wall_avg" in header


@pytest.mark.slow
def test_remote_platform_rpc_verifier(tmp_path, monkeypatch):
    """The batch-plane RPC (parallel/rpc_verifier.py): host A is flagged
    `device = true`, so its node process serves the shared
    BatchVerifierService over TCP and host B's chip-less process verifies
    every candidate through it — the fleet topology where one accelerator
    host serves all others (BASELINE.json north_star). Asserts the run
    completes AND that host B actually shipped candidates over the link
    (rpc counters on the monitor plane)."""
    from handel_tpu.sim.config import HostSpec
    from handel_tpu.sim.platform import run_simulation

    monkeypatch.setenv("HANDEL_TPU_PLATFORM", "cpu")
    cfg = SimConfig(
        network="udp",
        scheme="bn254-jax",
        batch_size=8,
        shared_verifier=True,
        max_timeout_s=900.0,
        hosts=[
            HostSpec(
                connect="local", workdir=str(tmp_path / "hostA"), device=True
            ),
            HostSpec(connect="local", workdir=str(tmp_path / "hostB")),
        ],
        runs=[
            RunConfig(
                nodes=8,
                threshold=5,
                processes=1,
                handel=HandelParams(period_ms=50.0, timeout_ms=200.0),
            )
        ],
    )
    results = asyncio.run(
        run_simulation(cfg, str(tmp_path / "out"), platform="remote")
    )
    res = results[0]
    if not res.ok:
        for out, err in res.outputs:
            print(out.decode(errors="replace"))
            print(err.decode(errors="replace"))
    assert res.ok
    rows = list(csv.DictReader(open(res.csv_path)))
    # host B's process sent candidates over the link; host A served them
    assert float(rows[0]["device_rpc_rpcSentCandidates_sum"]) > 0
    assert float(rows[0]["device_rpcserve_rpcServedCandidates_sum"]) > 0
    assert float(rows[0]["device_rpc_rpcLinkErrors_sum"]) == 0


def test_localhost_platform_base_port(tmp_path):
    """With base_port set the localhost platform assigns node i the fixed
    port base_port + i instead of probing — probing holds two fds per
    port simultaneously, which trips the fd limit at committee sizes like
    16384 (the 16k capture's failure mode)."""
    import csv as _csv

    from handel_tpu.sim.platform import run_simulation

    base = 13500  # below the 16000+ fixed ranges used by capture TOMLs
    cfg = SimConfig(
        network="udp",
        scheme="fake",
        base_port=base,
        max_timeout_s=60.0,
        runs=[RunConfig(nodes=8, threshold=5, processes=2)],
    )
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    assert results[0].ok
    with open(str(tmp_path / "registry_0.csv")) as f:
        rows = list(_csv.reader(f))
    assert [r[1] for r in rows] == [
        f"127.0.0.1:{base + i}" for i in range(8)
    ]


def test_port_plan_validates_bounds():
    """A base_port without room for the reserved -2/-3 slots or whose
    range runs past 65535 must fail immediately, not as a barrier stall
    after max_timeout_s (port 0/negative/out-of-range binds misbehave
    deep inside node processes)."""
    import pytest

    from handel_tpu.sim.platform import port_plan

    with pytest.raises(ValueError):
        port_plan(SimConfig(base_port=2), 8)
    with pytest.raises(ValueError):
        port_plan(SimConfig(base_port=65530), 8)
    node_ports, master, monitor, verifier = port_plan(
        SimConfig(base_port=18000), 8
    )
    assert node_ports == list(range(18000, 18008))
    assert (master, monitor, verifier) == (17998, 17999, 17997)


def test_preflight_ports_detects_conflict():
    """The fixed-plan pre-flight fails fast with the conflicting port
    named when something already holds one."""
    import socket

    import pytest

    from handel_tpu.sim.platform import free_ports, preflight_ports

    port = free_ports(1)[0]
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", port))
    try:
        with pytest.raises(OSError, match=str(port)):
            preflight_ports([port])
    finally:
        s.close()
    preflight_ports([port])  # released: now clean


def test_localhost_platform_bn254_real_crypto(tmp_path):
    """Small run with real BN254 host crypto end-to-end over real sockets."""
    cfg = SimConfig(
        network="udp",
        scheme="bn254",
        max_timeout_s=120.0,
        runs=[RunConfig(nodes=4, threshold=3, processes=2)],
    )

    async def go():
        plat = LocalhostPlatform(cfg, str(tmp_path))
        return await plat.start_run(0)

    res = asyncio.run(go())
    if not res.ok:
        for out, err in res.outputs:
            print(out.decode(errors="replace"))
            print(err.decode(errors="replace"))
    assert res.ok


def test_standalone_master_with_node_processes(tmp_path):
    """Multi-host form: a standalone master process (sim/master.py,
    reference simul/master/main.go:36-118) + node processes connecting to
    it over sockets, stats CSV written at END."""
    import asyncio
    import sys

    from handel_tpu.models.registry import new_scheme
    from handel_tpu.sim import keys as simkeys
    from handel_tpu.sim.config import SimConfig, RunConfig, dump_config
    from handel_tpu.sim.platform import free_ports

    async def go():
        n = 4
        cfg = SimConfig(network="udp", scheme="fake", runs=[RunConfig(nodes=n)])
        scheme = new_scheme("fake")
        ports = free_ports(n + 2)
        addrs = [f"127.0.0.1:{p}" for p in ports[:n]]
        recs = simkeys.generate_nodes(scheme, addrs)
        reg_path = str(tmp_path / "reg.csv")
        simkeys.write_registry_csv(reg_path, recs)
        cfg_path = str(tmp_path / "cfg.toml")
        with open(cfg_path, "w") as f:
            f.write(dump_config(cfg))
        csv_path = str(tmp_path / "stats.csv")
        import os

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "PYTHONPATH": repo_root}
        master = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "handel_tpu.sim.master",
            "--port", str(ports[n]), "--monitor-port", str(ports[n + 1]),
            "--expected", str(n), "--csv", csv_path, "--timeout", "60",
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE,
            env=env,
        )
        node = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "handel_tpu.sim.node",
            "--config", cfg_path, "--registry", reg_path,
            "--master", f"127.0.0.1:{ports[n]}",
            "--monitor", f"127.0.0.1:{ports[n+1]}",
            "--run", "0", "--ids", ",".join(map(str, range(n))),
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE,
            env=env,
        )
        (m_out, m_err), (n_out, n_err) = await asyncio.wait_for(
            asyncio.gather(master.communicate(), node.communicate()), 90
        )
        assert master.returncode == 0, m_err.decode()
        assert node.returncode == 0, n_err.decode()
        assert b"END released" in m_out
        with open(csv_path) as f:
            header = f.readline()
        assert "sigen_wall" in header

    asyncio.run(go())


def test_stats_percentile_filter():
    """DataFilter drops samples above the configured percentile before
    aggregation (stats.go DataFilter)."""
    from handel_tpu.sim.monitor import DataFilter, Stats

    stats = Stats(data_filter=DataFilter({"lat_wall": 50.0}))
    for v in (1.0, 2.0, 3.0, 100.0):
        stats.update("lat_wall", v)
        stats.update("other", v)
    row = dict(zip(stats.columns(), stats.row()))
    assert row["lat_wall_max"] <= 3.0  # outlier filtered
    assert row["other_max"] == 100.0  # unconfigured key passes through


def test_evaluator_knob_roundtrip(tmp_path):
    cfg = SimConfig(
        scheme="fake",
        runs=[RunConfig(nodes=8, handel=HandelParams(evaluator="fifo"))],
    )
    path = tmp_path / "sim.toml"
    path.write_text(dump_config(cfg))
    back = load_config(str(path))
    assert back.runs[0].handel.evaluator == "fifo"
    from handel_tpu.core.processing import FifoProcessing

    c = back.runs[0].handel.to_config(5, seed=1)
    assert c.new_processing is FifoProcessing


@pytest.mark.slow
def test_localhost_platform_256_nodes(tmp_path):
    """Reference-scale single-host run: 256 nodes, 8 processes, 99%
    threshold. Regression for the free_ports ephemeral-range race that
    deadlocked runs past ~128 sockets (platform.py free_ports)."""
    from handel_tpu.sim.platform import run_simulation

    cfg = SimConfig(
        network="udp",
        scheme="fake",
        max_timeout_s=120.0,
        runs=[
            RunConfig(
                nodes=256,
                threshold=254,
                processes=8,
                handel=HandelParams(period_ms=50.0, timeout_ms=100.0),
            )
        ],
    )
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    assert results[0].ok, [e.decode(errors="replace")[-2000:] for _, e in results[0].outputs]
    rows = list(csv.DictReader(open(results[0].csv_path)))
    assert float(rows[0]["nodes"]) == 256
    assert float(rows[0]["sigen_wall_avg"]) > 0


@pytest.mark.slow
def test_localhost_platform_2000_nodes_invariant(tmp_path):
    """Reference-scale nightly tier: 2000 nodes, 99% threshold, fake crypto
    (handel_test.go:71-84 scale + simul/plots/csv N=2000 rows). Asserts the
    protocol-convergence invariant instead of eyeballing it: signatures
    checked per node lands in the reference's band (~60/node at N=2000-4000,
    handel_0failing_99thr.csv: 61.8) — pacing knobs match the captured
    1024-node run (one shared CPU core: 200 ms period, slow timeouts)."""
    from handel_tpu.sim.platform import run_simulation

    cfg = SimConfig(
        network="udp",
        scheme="fake",
        # one shared core: 2000 asyncio nodes start up + converge slowly;
        # the barrier window must absorb both (the 1024-node run needed
        # ~1/3 of this)
        max_timeout_s=2400.0,
        runs=[
            RunConfig(
                nodes=2000,
                threshold=1980,
                processes=4,
                # pacing matters for the INVARIANT, not just wall time: the
                # period must be long enough for the starved core to drain a
                # whole round's traffic, or every resend round re-verifies
                # incrementally-improved aggregates and sigs-checked scales
                # with (wall/period) instead of staying ~60 (a 200 ms period
                # here measured 229 checked over a 33-minute crawl)
                handel=HandelParams(period_ms=1000.0, timeout_ms=2000.0),
            )
        ],
    )
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    assert results[0].ok, [
        e.decode(errors="replace")[-2000:] for _, e in results[0].outputs
    ]
    rows = list(csv.DictReader(open(results[0].csv_path)))
    assert float(rows[0]["nodes"]) == 2000
    checked = float(rows[0]["sigs_sigCheckedCt_avg"])
    # the invariant: log-structured aggregation, NOT O(N) flooding. The
    # reference averages 61.8 at N=4000 / 99%; the captured 1024-node run
    # measured 59.0. Band kept generous for scheduler jitter.
    assert 30.0 <= checked <= 120.0, f"sigs checked/node = {checked}"


@pytest.mark.slow
def test_localhost_platform_bn254_jax_shared_verifier(tmp_path, monkeypatch):
    """Simulation with verification on the device path: scheme bn254-jax +
    the shared BatchVerifierService fusing co-located nodes' requests into
    one launch per batch (sim/node.py scheme.constructor.Device dispatch).
    Node subprocesses take the CPU backend via HANDEL_TPU_PLATFORM."""
    from handel_tpu.sim.platform import run_simulation

    monkeypatch.setenv("HANDEL_TPU_PLATFORM", "cpu")
    cfg = SimConfig(
        network="udp",
        scheme="bn254-jax",
        batch_size=8,
        shared_verifier=True,
        max_timeout_s=900.0,
        runs=[
            RunConfig(
                nodes=8,
                threshold=5,
                processes=1,
                handel=HandelParams(period_ms=20.0),
            )
        ],
    )
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    assert results[0].ok, [
        e.decode(errors="replace")[-2000:] for _, e in results[0].outputs
    ]
    rows = list(csv.DictReader(open(results[0].csv_path)))
    assert float(rows[0]["sigs_sigCheckedCt_avg"]) > 0


@pytest.mark.slow
def test_localhost_platform_mesh_sharded_verifier(tmp_path, monkeypatch):
    """Simulation with the verification plane sharded over a device mesh:
    the `mesh_devices` TOML knob routes the shared BatchVerifierService's
    BN254Device through the shard_map kernels (parallel/sharding.py) on
    virtual CPU devices forced inside the node subprocess (sim/node.py)."""
    from handel_tpu.sim.platform import run_simulation

    monkeypatch.setenv("HANDEL_TPU_PLATFORM", "cpu")
    cfg = SimConfig(
        network="udp",
        scheme="bn254-jax",
        batch_size=8,
        shared_verifier=True,
        mesh_devices=4,  # 8-node registry: divisible; candidates pad
        max_timeout_s=900.0,
        runs=[
            RunConfig(
                nodes=8,
                threshold=5,
                processes=1,
                handel=HandelParams(period_ms=20.0),
            )
        ],
    )
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    assert results[0].ok, [
        e.decode(errors="replace")[-2000:] for _, e in results[0].outputs
    ]
    rows = list(csv.DictReader(open(results[0].csv_path)))
    assert float(rows[0]["sigs_sigCheckedCt_avg"]) > 0
