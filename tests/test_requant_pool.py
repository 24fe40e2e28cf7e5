"""Requant worker pool sizing (ISSUE 4 / ISSUE 5 satellites).

The shared pool is sized from the affinity mask, capped by the cgroup's
``cpu.max`` bandwidth quota — the signal no affinity mask reflects —
with ``EDTPU_REQUANT_WORKERS`` as the operator override.
"""

import easydarwin_tpu.hls.requant as rq


def _reset_cache():
    rq._sizing_cache = None


def test_pool_workers_env_override(monkeypatch):
    _reset_cache()
    monkeypatch.setenv("EDTPU_REQUANT_WORKERS", "3")
    assert rq.pool_workers() == 3
    monkeypatch.setenv("EDTPU_REQUANT_WORKERS", "bogus")
    _reset_cache()
    assert rq.pool_workers() >= 1


# -- cpu.max bandwidth-quota sizing (ISSUE 5 satellite) -------------------
# pool_sizing reads the cgroup quota and records which signal won, so the
# bench JSON carries the rationale.

def test_sizing_one_cpu_mask_trusts_bandwidth_quota():
    s = rq.pool_sizing(affinity=1, quota=2.0, cpu_count=8)
    assert s["workers"] == 2 and s["source"] == "cpu_max_quota"


def test_sizing_quota_caps_wide_affinity():
    """Big node, throttled cgroup: affinity says 96, cpu.max says 2 —
    sizing to 96 trades throughput for preemption thrash."""
    s = rq.pool_sizing(affinity=96, quota=2.4, cpu_count=96)
    assert s["workers"] == 2 and s["source"] == "cpu_max_cap"


def test_sizing_no_quota_uses_affinity():
    s = rq.pool_sizing(affinity=4, quota=None, cpu_count=8)
    assert s["workers"] == 4 and s["source"] == "affinity"
    # quota wider than affinity: affinity is the binding constraint
    s = rq.pool_sizing(affinity=4, quota=8.0, cpu_count=8)
    assert s["workers"] == 4 and s["source"] == "affinity"


def test_sizing_sub_cpu_quota_floors_at_one():
    s = rq.pool_sizing(affinity=4, quota=0.5, cpu_count=8)
    assert s["workers"] == 1 and s["source"] == "cpu_max_cap"


def test_sizing_quota_never_exceeds_cpu_count():
    s = rq.pool_sizing(affinity=1, quota=64.0, cpu_count=2)
    assert s["workers"] == 2 and s["source"] == "cpu_max_quota"


def test_sizing_rationale_surfaced():
    """The decision inputs ride along for the bench JSON extra."""
    s = rq.pool_sizing(affinity=3, quota=2.0, cpu_count=4)
    assert set(s) == {"workers", "source", "affinity_cpus", "quota_cpus",
                      "cpu_count"}
    assert s["affinity_cpus"] == 3 and s["quota_cpus"] == 2.0


def test_cgroup_quota_parse_shapes(tmp_path, monkeypatch):
    """The live probe on THIS host returns a positive number or None —
    both acceptable; the decision logic above is what's pinned."""
    q = rq._cgroup_quota_cpus()
    assert q is None or q > 0


def test_cgroup_quota_reads_own_nested_cgroup(tmp_path):
    """The quota lives in the PROCESS's cgroup, not the root: a systemd
    CPUQuota= service sits in system.slice/<svc> where the root cpu.max
    reads 'max'.  The effective limit is the minimum along the chain."""
    root = tmp_path / "cgroup"
    svc = root / "system.slice" / "svc"
    svc.mkdir(parents=True)
    (root / "cpu.max").write_text("max 100000\n")
    (root / "system.slice" / "cpu.max").write_text("800000 100000\n")
    (svc / "cpu.max").write_text("400000 100000\n")
    proc = tmp_path / "proc_cgroup"
    proc.write_text("0::/system.slice/svc\n")
    q = rq._cgroup_quota_cpus(proc_cgroup=str(proc), fs_root=str(root))
    assert q == 4.0                      # min(8.0 slice, 4.0 own)

    # v1 hierarchy shape (controller line, cfs files)
    v1 = tmp_path / "cg1"
    (v1 / "cpu" / "docker" / "c1").mkdir(parents=True)
    (v1 / "cpu" / "docker" / "c1" / "cpu.cfs_quota_us").write_text(
        "200000")
    (v1 / "cpu" / "docker" / "c1" / "cpu.cfs_period_us").write_text(
        "100000")
    proc1 = tmp_path / "proc_cgroup_v1"
    proc1.write_text("3:cpu,cpuacct:/docker/c1\n")
    q = rq._cgroup_quota_cpus(proc_cgroup=str(proc1), fs_root=str(v1))
    assert q == 2.0

    # no quota anywhere → None (root says max, no own entry)
    proc2 = tmp_path / "proc_cgroup_none"
    proc2.write_text("0::/\n")
    assert rq._cgroup_quota_cpus(proc_cgroup=str(proc2),
                                 fs_root=str(root / "empty")) is None
