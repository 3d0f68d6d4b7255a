"""The files-sharded deployment on several devices: placement, parity,
the mesh taken from the state, and the ``shard.gather`` stage.

The multi-device cases run in one subprocess with eight virtual CPU
devices (``XLA_FLAGS`` must be set before JAX is imported, and this
process keeps its single device; see ``test_dryrun_integration``). The
subprocess is this file run as a script: it prints one JSON object of
findings, which the parametrised tests below read case by case.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEVICES = 8
SHARDS = 4
FILES_PER_SHARD = 32
SCHEMES = ("idl", "rh")
THETAS = (1.0, 0.9)


def _config(scheme: str, n_files: int):
    from repro.serving.genesearch import GeneSearchConfig
    return GeneSearchConfig(name="t", n_files=n_files, m=1 << 16, k=31, t=12,
                            L=1 << 10, eta=2, read_len=120, scheme=scheme)


def _reads(archive, n: int, seed: int):
    """Half cut from indexed files, half random; 100 bp each."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if i % 2:
            out.append(rng.integers(0, 4, size=100, dtype=np.uint8))
        else:
            g = archive[int(rng.integers(len(archive)))].genome
            s = int(rng.integers(0, len(g) - 100))
            out.append(np.asarray(g[s:s + 100]))
    return out


def _findings() -> dict:
    """Every multi-device check, run on ``N_DEVICES`` virtual devices."""
    import jax
    import numpy as np

    from repro import obs
    from repro.data import genome
    from repro.launch.serve import build_index, build_sharded_index
    from repro.obs import metrics as obs_metrics
    from repro.serving import GeneSearchService, ServiceConfig

    devs = jax.devices()
    n_files = SHARDS * FILES_PER_SHARD
    archive = genome.synth_archive(n_files=n_files, genome_len=400, seed=3)
    reads = _reads(archive, 24, seed=4)
    out: dict = {"devices": len(devs)}

    def answers(eng, backend, theta):
        svc = GeneSearchService(eng, ServiceConfig(
            theta=theta, backend=backend, max_batch=8))
        return [list(r.file_ids) for r in svc.search(reads)]

    for scheme in SCHEMES:
        cfg = _config(scheme, n_files)
        whole = build_index(cfg, archive)
        for first in (0, N_DEVICES - SHARDS):
            mine = devs[first:first + SHARDS]
            eng = build_sharded_index(cfg, archive, mine)
            words = eng.words
            if scheme == "idl":
                cols = FILES_PER_SHARD // 32
                out[f"placement/{first}"] = [
                    [s.index[1].start // cols, mine.index(s.device)]
                    for s in words.addressable_shards]
                out[f"words_equal/{first}"] = bool(np.array_equal(
                    np.asarray(words), np.asarray(whole.words)))
                per = eng.query_batch(np.stack(reads[:8]),
                                      backend="sharded")
                out[f"served_on/{first}"] = sorted(
                    mine.index(d) if d in mine else -1 - devs.index(d)
                    for d in per.sharding.device_set)
            for theta in THETAS:
                got = answers(eng, "sharded", theta)
                want = answers(whole, "jnp", theta)
                out[f"parity/{scheme}/{theta}/{first}"] = {
                    "equal": got == want,
                    "answered": sum(bool(a) for a in want)}

    eng = build_sharded_index(_config("idl", n_files), archive,
                              devs[:SHARDS])
    for enabled in (True, False):
        obs.reset()
        obs.set_enabled(enabled)
        answers(eng, "sharded", 1.0)
        hist = obs_metrics.DEFAULT.snapshot().get("hists", {}).get(
            "serving.stage_ms", {}).get("stage=shard.gather")
        out[f"stage/{enabled}"] = hist["count"] if hist else 0
    return out


@pytest.fixture(scope="module")
def findings():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{N_DEVICES}")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == N_DEVICES
    return out


@pytest.mark.parametrize("first", [0, N_DEVICES - SHARDS])
def test_shard_d_is_on_device_d_only(findings, first):
    assert sorted(findings[f"placement/{first}"]) == [
        [d, d] for d in range(SHARDS)]
    assert findings[f"words_equal/{first}"]


@pytest.mark.parametrize("first", [0, N_DEVICES - SHARDS])
def test_served_on_the_mesh_that_holds_the_state(findings, first):
    """A state on devices 4..7 of the 8 is served on those four, not
    resharded over every device of the host."""
    assert findings[f"served_on/{first}"] == list(range(SHARDS))


@pytest.mark.parametrize("first", [0, N_DEVICES - SHARDS])
@pytest.mark.parametrize("theta", THETAS)
@pytest.mark.parametrize("scheme", SCHEMES)
def test_sharded_service_equals_jnp_over_the_whole_archive(
        findings, scheme, theta, first):
    found = findings[f"parity/{scheme}/{theta}/{first}"]
    assert found["equal"]
    assert found["answered"] >= 6      # the indexed half mostly answers


@pytest.mark.parametrize("enabled", [True, False])
def test_shard_gather_stage_is_recorded_only_when_enabled(findings,
                                                          enabled):
    count = findings[f"stage/{enabled}"]
    assert (count > 0) if enabled else (count == 0)


@pytest.mark.parametrize("n_devices, n_files", [(3, 64), (4, 64)],
                         ids=["uneven", "partial_words"])
def test_archive_must_split_into_whole_words(n_devices, n_files):
    import jax

    from repro.launch.serve import build_sharded_index
    with pytest.raises(ValueError, match="whole 32-file words"):
        build_sharded_index(_config("idl", n_files), [],
                            [jax.devices()[0]] * n_devices)


def test_an_unsharded_matrix_takes_the_default_mesh():
    import jax.numpy as jnp

    from repro.index import query
    assert query.mesh_of(jnp.zeros((8, 2), jnp.uint32)) is \
        query.default_mesh()
    assert query.mesh_of(None) is query.default_mesh()


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(REPO, "src"))
    print(json.dumps(_findings()))
