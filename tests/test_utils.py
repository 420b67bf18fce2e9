"""Checkpoint/resume tests."""

import numpy as np

from opensim_tpu.encoding.state import ClusterEncoder
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.utils.checkpoint import load_state, save_state


def test_checkpoint_roundtrip(tmp_path):
    enc = ClusterEncoder()
    enc.add_nodes([fx.make_fake_node("n0"), fx.make_fake_node("n1")])
    enc.add_pod(fx.make_fake_pod("p0", "1", "1Gi"))
    ec, st, _meta = enc.build()
    path = str(tmp_path / "ckpt.npz")
    save_state(path, ec, st, extra={"round": 3})
    ec2, st2, extra = load_state(path)
    assert extra == {"round": 3}
    for a, b in zip(ec, ec2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(st, st2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # resuming the scan from a checkpoint gives identical results
    from opensim_tpu.engine.scheduler import schedule_pods, to_device

    tmpl = np.zeros(4, np.int32)
    valid = np.ones(4, bool)
    forced = np.zeros(4, bool)
    ecd, std = to_device(ec, st)
    ecd2, std2 = to_device(ec2, st2)
    out1 = schedule_pods(ecd, std, tmpl, valid, forced)
    out2 = schedule_pods(ecd2, std2, tmpl, valid, forced)
    np.testing.assert_array_equal(np.asarray(out1.chosen), np.asarray(out2.chosen))


def test_checkpoint_backfills_old_archives(tmp_path):
    """The NOTES.md invariant, previously untested: loading an archive
    written before EncodedCluster grew ``gc_mask`` and ``log_sizes`` must
    backfill both — gc_mask all-static (exactly the saved behavior) and
    log_sizes bit-identical to the shared table the encoder would build."""
    from opensim_tpu.encoding.dtypes import log_size_table

    enc = ClusterEncoder()
    enc.add_nodes([fx.make_fake_node("n0"), fx.make_fake_node("n1")])
    enc.add_pod(fx.make_fake_pod("p0", "1", "1Gi"))
    ec, st, _meta = enc.build()
    path = str(tmp_path / "old.npz")
    save_state(path, ec, st)

    # rewrite the archive WITHOUT the two newer fields, as a pre-gc_mask
    # checkpoint would have been written
    with np.load(path) as data:
        stripped = {
            k: data[k] for k in data.files if k not in ("ec_gc_mask", "ec_log_sizes")
        }
    np.savez_compressed(path, **stripped)

    ec2, st2, _extra = load_state(path)
    np.testing.assert_array_equal(
        np.asarray(ec2.gc_mask), np.zeros((np.asarray(ec.alloc).shape[1],), dtype=bool)
    )
    np.testing.assert_array_equal(
        np.asarray(ec2.log_sizes), log_size_table(np.asarray(ec.alloc).shape[0])
    )
    # every other field survives untouched
    for name, a in ec._asdict().items():
        if name in ("gc_mask", "log_sizes"):
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(getattr(ec2, name)))

    # and the resumed state still schedules identically to the original
    from opensim_tpu.engine.scheduler import schedule_pods, to_device

    tmpl = np.zeros(2, np.int32)
    valid = np.ones(2, bool)
    forced = np.zeros(2, bool)
    out1 = schedule_pods(*to_device(ec, st), tmpl, valid, forced)
    out2 = schedule_pods(*to_device(ec2, st2), tmpl, valid, forced)
    np.testing.assert_array_equal(np.asarray(out1.chosen), np.asarray(out2.chosen))


def test_progress_spinner_and_bar(monkeypatch):
    """pterm-parity progress (simulator.go:311-321): the spinner leaves a
    final tally line and stays silent when disabled."""
    import io
    import time as _time

    from opensim_tpu.utils import progress

    monkeypatch.delenv("OPENSIM_NO_PROGRESS", raising=False)

    buf = io.StringIO()
    with progress.Spinner("work", stream=buf, enabled=True):
        _time.sleep(0.25)
    text = buf.getvalue()
    assert "work" in text and "✓" in text

    silent = io.StringIO()
    with progress.Spinner("quiet", stream=silent, enabled=False):
        pass
    assert silent.getvalue() == ""

