"""Ephemeral-disk enforcement (alloc_dir.go:618 disk watcher) and the
chroot Embed (alloc_dir.go:348, exec_linux.go:48): an over-quota task
group is killed with a disk-exceeded event, and a chrooted exec task
finds its toolchain inside the populated task dir."""

import os
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.client import alloc_runner as ar_mod
from nomad_tpu.client.alloc_runner import AllocRunner
from nomad_tpu.client.allocdir import CHROOT_ENV, embed_chroot
from nomad_tpu.structs import consts


def wait_until(fn, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def test_disk_exceeded_kills_tasks(tmp_path, monkeypatch):
    monkeypatch.setattr(ar_mod, "DISK_WATCH_INTERVAL", 0.1)
    alloc = mock.alloc()
    tg = alloc.job.task_groups[0]
    tg.ephemeral_disk.size_mb = 1
    task = tg.tasks[0]
    task.driver = "mock_driver"
    task.config = {"run_for": 30.0}
    alloc.task_resources = {task.name: task.resources}
    synced = []
    runner = AllocRunner(alloc, str(tmp_path),
                         lambda a: synced.append(a.client_status), 5.0)
    runner.run()
    assert wait_until(
        lambda: (alloc.task_states.get(task.name) or mock.alloc()
                 ).state == consts.TASK_STATE_RUNNING
        if alloc.task_states.get(task.name) else False)

    # Blow the 1MB quota from inside the alloc dir.
    hog = os.path.join(runner.alloc_dir.shared_dir, "data", "hog")
    with open(hog, "wb") as f:
        f.write(b"\x00" * (3 * 1024 * 1024))

    assert wait_until(
        lambda: alloc.task_states[task.name].state == consts.TASK_STATE_DEAD)
    ts = alloc.task_states[task.name]
    assert ts.failed, "disk-exceeded kill must fail the task"
    assert any(e.type == consts.TASK_EVENT_DISK_EXCEEDED for e in ts.events)
    assert any("exceeds" in (e.message or "") for e in ts.events)
    assert wait_until(
        lambda: alloc.client_status == consts.ALLOC_CLIENT_FAILED)


def test_disk_within_quota_untouched(tmp_path, monkeypatch):
    monkeypatch.setattr(ar_mod, "DISK_WATCH_INTERVAL", 0.1)
    alloc = mock.alloc()
    alloc.job.type = "batch"  # completes instead of restarting
    tg = alloc.job.task_groups[0]
    tg.ephemeral_disk.size_mb = 100
    task = tg.tasks[0]
    task.driver = "mock_driver"
    task.config = {"run_for": 0.5}
    alloc.task_resources = {task.name: task.resources}
    runner = AllocRunner(alloc, str(tmp_path), lambda a: None, 5.0)
    runner.run()
    assert wait_until(
        lambda: alloc.task_states.get(task.name) is not None
        and alloc.task_states[task.name].state == consts.TASK_STATE_DEAD)
    ts = alloc.task_states[task.name]
    assert not ts.failed
    assert not any(
        e.type == consts.TASK_EVENT_DISK_EXCEEDED for e in ts.events)


def test_embed_chroot_links_files_and_symlinks(tmp_path):
    src = tmp_path / "hostroot"
    (src / "inner").mkdir(parents=True)
    (src / "tool").write_text("#!/bin/sh\necho hi\n")
    (src / "inner" / "lib.so.1.2").write_text("lib")
    os.symlink("lib.so.1.2", src / "inner" / "lib.so")

    chroot = tmp_path / "chroot"
    chroot.mkdir()
    embed_chroot(str(chroot), {str(src): "opt/host", "/nonexistent": "x"})

    assert (chroot / "opt/host/tool").read_text().startswith("#!")
    # Hardlinked, not copied: same inode.
    assert (chroot / "opt/host/tool").stat().st_ino == (src / "tool").stat().st_ino
    # Symlink preserved as a symlink with its relative target.
    link = chroot / "opt/host/inner/lib.so"
    assert link.is_symlink() and os.readlink(link) == "lib.so.1.2"
    assert not (chroot / "x").exists()


@pytest.mark.slow  # embeds the entire host toolchain (/usr, /lib, ...)
# by hardlink-or-copy: on overlayfs containers the copy fallback alone
# runs for minutes — a real-chroot integration test, not a unit test.
@pytest.mark.skipif(os.geteuid() != 0, reason="chroot requires root")
def test_chroot_exec_runs_in_populated_root(tmp_path):
    """A chrooted exec task runs /bin/sh from the EMBEDDED toolchain
    and can only see the task dir as its filesystem."""
    alloc = mock.alloc()
    alloc.job.type = "batch"  # completes instead of restarting
    tg = alloc.job.task_groups[0]
    task = tg.tasks[0]
    task.driver = "exec"
    task.config = {
        "command": "/bin/sh",
        "args": ["-c", "ls / > /local/rootlist; echo ok > /local/out"],
        "chroot": True,
    }
    alloc.task_resources = {task.name: task.resources}
    runner = AllocRunner(alloc, str(tmp_path), lambda a: None, 5.0)
    runner.run()
    assert wait_until(
        lambda: alloc.task_states.get(task.name) is not None
        and alloc.task_states[task.name].state == consts.TASK_STATE_DEAD,
        timeout=60.0)
    ts = alloc.task_states[task.name]
    assert not ts.failed, [
        (e.type, e.message, e.driver_error) for e in ts.events]
    task_dir = runner.alloc_dir.task_dirs[task.name]
    out = os.path.join(task_dir, "local", "out")
    assert wait_until(lambda: os.path.exists(out), timeout=10.0)
    assert open(out).read().strip() == "ok"
    # The task's / was the task dir: its listing has the embedded
    # toolchain and local/, not the host root's contents.
    rootlist = open(os.path.join(task_dir, "local", "rootlist")).read()
    assert "local" in rootlist and "bin" in rootlist
    assert "hostroot-canary" not in rootlist

def test_disk_used_counts_each_inode_once_and_prunes_embeds(tmp_path):
    """Accounting rules: a task's OWN hardlinks are charged once (not
    zero — that would let a task dodge the quota; not twice — that
    would overcharge), and the embedded chroot subtrees recorded in
    AGENT-owned state are excluded entirely."""
    from nomad_tpu.client.allocdir import AllocDir

    ad = AllocDir(str(tmp_path / "alloc1"))
    ad.build(["t"])
    data = os.path.join(ad.shared_dir, "data")

    big = os.path.join(data, "big")
    with open(big, "wb") as f:
        f.write(b"\x00" * (2 * 1024 * 1024))
    os.link(big, os.path.join(data, "big-link"))  # same inode
    # 2MB charged once, not 0 and not 4MB.
    used = ad.disk_used_mb()
    assert 1.9 < used < 2.5, used

    # Embed a host tree into the task chroot through the AllocDir API:
    # the agent-recorded subtree prunes from accounting.
    src = tmp_path / "hosttree"
    src.mkdir()
    (src / "toolchain").write_bytes(b"\x00" * (3 * 1024 * 1024))
    ad.embed_chroot("t", {str(src): "opt/tools"})
    used_after = ad.disk_used_mb()
    assert used_after < used + 0.5, (
        f"embedded toolchain charged against the quota: {used_after}")

    # The prune record persists at the alloc ROOT (outside every
    # task-writable tree) and survives a client restart: a fresh
    # AllocDir over the same tree keeps pruning.
    ad2 = AllocDir(ad.root)
    ad2.task_dirs = dict(ad.task_dirs)
    assert ad2.disk_used_mb() < used + 0.5


def test_embed_records_prune_before_linking(tmp_path, monkeypatch):
    """The prune list must be registered BEFORE the embed starts: a
    host-toolchain embed can run for minutes and the disk watcher polls
    meanwhile — counting the half-built toolchain would falsely kill
    the alloc."""
    from nomad_tpu.client import allocdir as ad_mod
    from nomad_tpu.client.allocdir import AllocDir

    ad = AllocDir(str(tmp_path / "alloc1"))
    ad.build(["t"])
    seen = {}

    def fake_embed(root, sources=None):
        # At embed time the agent state must already prune the target.
        seen["recorded"] = list(ad._embedded.get("t", ()))
        return ad_mod.embed_rels(sources)

    monkeypatch.setattr(ad_mod, "embed_chroot", fake_embed)
    ad.embed_chroot("t", {"/bin": "opt/tools"})
    assert seen["recorded"] == ["opt"], seen


def test_exec_driver_rejects_task_config_chroot_env():
    """chroot_env is an operator (client config) setting; the exec
    driver must reject it in task config with a message that names the
    right home for the knob."""
    from nomad_tpu import mock
    from nomad_tpu.client.drivers.base import new_driver

    task = mock.job().task_groups[0].tasks[0]
    task.driver = "exec"
    task.config = {"command": "/bin/true",
                   "chroot_env": {"/etc/shadow": "etc/shadow"}}
    with pytest.raises(ValueError, match="client agent setting"):
        new_driver("exec").validate_config(task)


def test_disk_used_ignores_task_written_manifest(tmp_path):
    """An advisor finding of round 5: the disk watcher must not trust
    ANY file the task can write. A task forging an embed manifest inside its own dir
    (the pre-fix mechanism) gets charged anyway — only the agent's own
    embed_chroot registration prunes."""
    import json

    from nomad_tpu.client.allocdir import AllocDir

    ad = AllocDir(str(tmp_path / "alloc1"))
    ad.build(["t"])
    hog_dir = os.path.join(ad.task_dirs["t"], "local", "cache")
    os.makedirs(hog_dir)
    with open(os.path.join(hog_dir, "hog"), "wb") as f:
        f.write(b"\x00" * (4 * 1024 * 1024))
    # The task tries to exempt its writes the way the old manifest
    # reader would have allowed.
    with open(os.path.join(ad.task_dirs["t"], ".nomad-embed.json"),
              "w") as f:
        json.dump(["local"], f)
    assert ad.disk_used_mb() > 3.5, "task-forged manifest dodged the quota"
