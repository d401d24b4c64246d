"""Charliecloud-capsule workflow + site security policy tests."""
import json
import os
from pathlib import Path

import pytest

from repro.core import container as C
from repro.core import deploy as D
from repro.core.registry import OfflineViolation, default_index


@pytest.fixture
def pipeline():
    return D.DeploymentPipeline(index=default_index())


def test_build_requires_workstation():
    builder = C.ImageBuilder(default_index(), context=C.CLUSTER)
    with pytest.raises(OfflineViolation):
        builder.build(C.ImageDefinition("x", requirements=("numpy>=1.14",)))


def test_full_pipeline_and_run(tmp_path, pipeline):
    dep = pipeline.deploy(D.intel_tensorflow_image("t1"), tmp_path, nodes=4)
    assert dep.archive.exists() and dep.unpacked.exists()
    assert "mpiexec -n 4 -ppn 1 ch-run" in dep.slurm_script
    res = dep.run(lambda: os.environ["REPRO_CAPSULE"], ranks=3)
    assert [r.value for r in res] == ["t1"] * 3
    assert res[1].rank == 1 and res[1].world_size == 3


def test_env_scrubbed_and_restored(tmp_path, pipeline):
    dep = pipeline.deploy(D.intel_tensorflow_image("t2"), tmp_path)
    os.environ["SSH_AUTH_SOCK"] = "/tmp/ssh-evil"
    try:
        res = dep.run(lambda: os.environ.get("SSH_AUTH_SOCK", "SCRUBBED"))
        assert res[0].value == "SCRUBBED"
        assert os.environ["SSH_AUTH_SOCK"] == "/tmp/ssh-evil"  # restored
    finally:
        del os.environ["SSH_AUTH_SOCK"]


def test_pip_inside_capsule_dies(tmp_path, pipeline):
    dep = pipeline.deploy(D.intel_tensorflow_image("t3"), tmp_path)
    with pytest.raises(OfflineViolation):
        dep.run(lambda: C.capsule_pip_install("pandas"))


def test_image_immutability(tmp_path, pipeline):
    dep = pipeline.deploy(D.intel_tensorflow_image("t4"), tmp_path)

    def vandalize():
        root = Path(os.environ["REPRO_CAPSULE_ROOT"])
        (root / "image" / "manifest.json").write_text("{}")
        return True

    with pytest.raises(C.SecurityError, match="immutability"):
        dep.run(vandalize)
    # with -w (writeable) it is allowed, like ch-run -w
    dep2 = pipeline.deploy(D.intel_tensorflow_image("t5"), tmp_path)
    dep2.runtime.run(dep2.unpacked, vandalize, writeable=True)


def test_unpack_refuses_hash_mismatch(tmp_path):
    idx = default_index()
    b = C.ImageBuilder(idx)
    img1 = b.build(C.ImageDefinition("same-name", requirements=("numpy>=1.14",)))
    img2 = b.build(C.ImageDefinition("same-name", requirements=("six>=1.10",)))
    a1 = C.flatten(img1, tmp_path / "w1")
    a2 = C.flatten(img2, tmp_path / "w2")
    C.unpack(a1, tmp_path / "tmpfs")
    with pytest.raises(C.SecurityError, match="hash mismatch"):
        C.unpack(a2, tmp_path / "tmpfs")


def test_unpack_refuses_partial_tree(tmp_path):
    """A crashed prior ch-tar2dir leaves a partial dest (no manifest, or
    a corrupt one) — that must read as the same hash-mismatch refusal,
    not leak a FileNotFoundError / JSONDecodeError."""
    idx = default_index()
    img = C.ImageBuilder(idx).build(
        C.ImageDefinition("partial", requirements=("numpy>=1.14",)))
    archive = C.flatten(img, tmp_path / "w")
    dest = tmp_path / "tmpfs" / "partial"
    dest.mkdir(parents=True)              # partial tree: no manifest at all
    with pytest.raises(C.SecurityError, match="hash mismatch"):
        C.unpack(archive, tmp_path / "tmpfs")
    (dest / "image").mkdir()
    (dest / "image/manifest.json").write_text("{truncated")   # corrupt
    with pytest.raises(C.SecurityError, match="hash mismatch"):
        C.unpack(archive, tmp_path / "tmpfs")
    (dest / "image/manifest.json").write_text("{}")           # hashless
    with pytest.raises(C.SecurityError, match="hash mismatch"):
        C.unpack(archive, tmp_path / "tmpfs")


def test_interleaved_capsule_env_frames(tmp_path, pipeline):
    """Two in-process capsules interleaved non-LIFO (A enters, B enters,
    A exits, B exits): B's frame must survive A's exit intact, scrubbed
    vars stay scrubbed while any frame is live, and the last exit
    restores the host environment exactly.  The old snapshot/restore
    scheme failed all three."""
    dep_a = pipeline.deploy(D.intel_tensorflow_image("cap-a"), tmp_path)
    dep_b = pipeline.deploy(D.intel_tensorflow_image("cap-b"), tmp_path)
    os.environ["SSH_AUTH_SOCK"] = "/tmp/ssh-interleave"
    try:
        baseline = dict(os.environ)
        rt = dep_a.runtime
        man_a = json.loads(
            (dep_a.unpacked / "image/manifest.json").read_text())
        man_b = json.loads(
            (dep_b.unpacked / "image/manifest.json").read_text())
        cm_a = rt._capsule_env(dep_a.unpacked, man_a, None)
        cm_b = rt._capsule_env(dep_b.unpacked, man_b, None)
        cm_a.__enter__()
        assert os.environ["REPRO_CAPSULE"] == "cap-a"
        cm_b.__enter__()
        assert os.environ["REPRO_CAPSULE"] == "cap-b"  # last entrant wins
        cm_a.__exit__(None, None, None)                # non-LIFO exit
        assert os.environ["REPRO_CAPSULE"] == "cap-b"
        assert os.environ["REPRO_CAPSULE_ROOT"] == str(dep_b.unpacked)
        assert "SSH_AUTH_SOCK" not in os.environ       # still scrubbed
        cm_b.__exit__(None, None, None)
        assert dict(os.environ) == baseline            # exact restore
    finally:
        os.environ.pop("SSH_AUTH_SOCK", None)


def test_fn_receives_composed_capsule_env(tmp_path, pipeline):
    """Functions declaring a ``capsule_env`` parameter get the composed
    per-run frame directly — the race-free alternative to reading
    os.environ while another capsule may be live."""
    dep = pipeline.deploy(D.intel_tensorflow_image("t6"), tmp_path)
    res = dep.runtime.run(
        dep.unpacked,
        lambda capsule_env: (capsule_env["REPRO_CAPSULE"],
                             capsule_env["REPRO_NO_NETWORK"]),
        env={"EXTRA": "1"})
    assert res.value == ("t6", "1")


def test_site_policy_rejects_docker_singularity_admits_charliecloud():
    pol = C.SecurityPolicy()
    with pytest.raises(C.SecurityError):
        pol.admit(C.RUNTIME_PROFILES["docker"])
    with pytest.raises(C.SecurityError):
        pol.admit(C.RUNTIME_PROFILES["singularity"])
    pol.admit(C.RUNTIME_PROFILES["charliecloud"])  # no raise


def test_slurm_script_single_vs_multi():
    from repro.core import slurm
    s1 = slurm.render_script("j", "/img", "python", nodes=1)
    assert "mpiexec" not in s1 and "ch-run /img" in s1
    s2 = slurm.render_script("j", "/img", "python", nodes=16)
    assert "mpiexec -n 16 -ppn 1 ch-run /img" in s2


def test_slurm_omp_threads_clamp():
    from repro.core import slurm
    s = slurm.render_script("j", "/img", "python", threads_per_rank=96)
    assert "export OMP_NUM_THREADS=48" in s
    # a 1-cpu rank must not render OMP_NUM_THREADS=0 (would disable
    # the OpenMP runtime entirely on real systems)
    s1 = slurm.render_script("j", "/img", "python", threads_per_rank=1)
    assert "export OMP_NUM_THREADS=1" in s1


def test_slurm_env_values_are_shell_quoted():
    from repro.core import slurm
    s = slurm.render_script(
        "j", "/img", "python",
        env={"SPOOL": "/tmp/my spool/dir",
             "SPEC": '{"config": "qwen2-0.5b"}'})
    assert "export SPOOL='/tmp/my spool/dir'" in s
    assert """export SPEC='{"config": "qwen2-0.5b"}'""" in s
