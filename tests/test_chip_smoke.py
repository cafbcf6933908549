"""chip_smoke.py must not rot between chip runs.

The script is the gate every PR's chip run starts from; here, on the
CPU, tier-1 checks the two things a sandbox can: that the default
invocation refuses a box without a TPU (quickly, saying why, printing
no result), and — through the test-only ``--dry-run-cpu`` argument —
that every leg still runs end to end at toy size: real daemon
processes, a kill, a degraded read, a rebuild, every kernel in
interpret mode, the mesh legs on the virtual CPU devices, and the
fresh-process pass served entirely by the persistent compile cache.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=120, **env):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, timeout=timeout,
        capture_output=True, text=True, env=dict(os.environ, **env),
    )


def _result_lines(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_default_invocation_refuses_a_cpu():
    r = _run([], timeout=30, JAX_PLATFORMS="cpu")
    assert r.returncode not in (0, None)
    assert "no TPU visible" in r.stdout
    assert "platform=cpu" in r.stdout
    assert not _result_lines(r.stdout)
    assert "OK " not in r.stdout  # nothing was built or launched


def test_alone_outside_a_checkout_it_fails(tmp_path):
    lone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([], cwd=tmp_path, script=str(lone), timeout=30)
    assert r.returncode not in (0, None)
    assert "not inside a lizardfs-tpu checkout" in r.stdout
    assert not _result_lines(r.stdout)


def test_dry_run_drives_every_leg(tmp_path):
    cache = tmp_path / "cache"
    r = _run(["--dry-run-cpu"], timeout=600,
             JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.splitlines()
    assert lines[-1] == "DRY RUN (cpu) — not a chip result"
    assert not _result_lines(r.stdout)  # never a chip result line
    assert not any(line.startswith("FAIL") for line in lines)
    ok = [line for line in lines if line.startswith("OK ")]
    for needle in (
        "native library built", "cluster up: 1 master + 13 chunkservers",
        "write at goal ec(8,4)", "read both back cold",
        "read both back degraded", "full redundancy again",
        "no child process maps libtpu",
        "encode_with_checksums ec(8,4)", "encode_with_checksums ec(3,2)",
        "BIG_TILE_CONFIG", "ROOFLINE_CONFIG", "checksum over",
        "recover 1 lost", "recover 4 lost", "fused_decode_verify",
        "pallas encode", "xor_parity xor3",
        "dryrun_multichip(4)", "ShardedTpuChunkEncoder.recover",
    ):
        assert any(needle in line for line in ok), needle
    # the cache went where the variable said, and the fresh process
    # found every program there
    assert f"compile cache: {cache}" in r.stdout
    assert any(cache.iterdir())
    assert "warm pass" in r.stdout
    assert "persistent cache 0 misses" in [
        line for line in lines if "warm pass:" in line
    ][0]
