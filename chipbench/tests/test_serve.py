"""The served cell at a tiny size on the CPU, end to end.

A benchmark root built in a temporary directory holds the real
``qwen2_5_3b.smc_batch`` cell with its configuration, traffic and
workload files cut to a few layers of small widths (grouped-query heads,
biases and the tied head kept) and a few requests.  The tests:

* a sound run reads ``correct`` true, reports ``tokens_per_s`` and
  ``setup_s``, and compiles nothing inside the window; a traced run reads
  every ``.serve`` metric from its CPU trace;
* set-up does the same work for every seed;
* the reference matches the engine's prefill-then-decode logits;
* the control (the reference with float8 products in the program's place)
  reads ``correct`` false, by its own comparison;
* faults planted in the timed path read ``correct`` false: one particle's
  table row pointed at a sibling's pages (a missed copy), logits one tick
  stale, half of the batch left out of the decode, the KV cache returned
  unchanged, a logit altered where it is produced, and a token fed to the
  model other than the one the scheduler committed.  The cell runs on one
  chip, so no exchange between chips exists to leave out;
* the harness finds the new cell, driver and metrics by name.
"""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import backlog
from chipbench import run as run_lib
from chipbench.bench import Bench
from chipbench.counts import qwen2_5_3b as counts
from chipbench.reference import qwen2_5_3b as ref_lib
from chipbench.tests.conftest import REPO

CELL = "qwen2_5_3b.smc_batch"
CONFIG = REPO / "chipbench" / "configs" / "qwen2_5_3b.json"
SERVE_METRICS = ("device_idle_share.serve", "step_mfu.serve", "decode_ms.serve",
                 "launches_per_tick.serve", "kv_peak_blocks.serve")
# The served cell's entries in ``BENCHMARK.json``.  The tests run the cell
# from a root whose ``BENCHMARK.json`` is the repository's with these
# added where it lacks them; the bound is set from chip readings when the
# cell is admitted.
CELL_ONLY = {"moves": "tokens_per_s", "workloads": [CELL]}
TICK_LAYER = "serving tick (serving/scheduler.py Scheduler.step)"
SERVE_ENTRIES = {
    "configs": [{
        "name": "qwen2_5_3b", "source": "https://huggingface.co/Qwen/Qwen2.5-3B",
        "file": "chipbench/configs/qwen2_5_3b.json", "reduced": [],
        "why": "Qwen2.5-3B whole at its published widths in bf16 on the served path: "
               "grouped-query paged KV with COW forks, SMC decode, the scheduler's host loop"}],
    "workloads": [{
        "name": CELL, "config": "qwen2_5_3b", "traffic": "smc_backlog", "chips": 1,
        "why": "backlog of 96 x 8 particles, prompts 256-2048, 256 tokens each: decode of 48 "
               "full slots with forks and COW in the KV pool; admission and departures lie "
               "outside the window"}],
    "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
                    "bound": None, "source": "host_clock", "workloads": [CELL]}],
    "per_layer": [
        {"name": "device_idle_share.serve", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device", **CELL_ONLY},
        {"name": "step_mfu.serve", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": TICK_LAYER, **CELL_ONLY},
        {"name": "decode_ms.serve", "unit": "ms", "better": "lower", "source": "device_trace",
         "layer": "decode step (serving/engine.py _decode_step, kernels/paged_attention)",
         **CELL_ONLY},
        {"name": "launches_per_tick.serve", "unit": "programs", "better": "lower",
         "source": "device_trace", "layer": TICK_LAYER, **CELL_ONLY},
        {"name": "kv_peak_blocks.serve", "unit": "blocks", "better": "lower",
         "source": "program_counter", "layer": "KV pool (serving/kv_cache.py, core/pool.py)",
         **CELL_ONLY},
    ],
}
SMOKE = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
             dtype="float32", param_dtype="float32")


def smoke_model():
    model = dict(json.loads(CONFIG.read_text())["model"])
    model.update(SMOKE)
    return model


def serve_root(tmp):
    """A benchmark root whose served cell is the real one at a tiny size."""
    root = tmp / "bench"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    c = root / "chipbench"

    def edit(rel, **changes):
        obj = json.loads((c / rel).read_text())
        for key, value in changes.items():
            if isinstance(value, dict):
                obj[key].update(value)
            else:
                obj[key] = value
        (c / rel).write_text(json.dumps(obj))

    edit("configs/qwen2_5_3b.json", model=smoke_model(),
         serve={"max_seqs": 8, "kv_block_size": 4, "kv_num_blocks": 64, "max_len": 40})
    edit("traffic/smc_backlog.json", requests=4, particles=4, prompt_tokens=[4, 16],
         prompt_buckets=[8, 12, 16], steps=24)
    edit(f"workloads/{CELL}.json", trace={"start_share": 0.2, "seconds": 0.3},
         check={"particles": 4, "ticks": 4})
    (root / "BENCHMARK.json").write_text(json.dumps(serve_benchmark()))
    return root


def serve_benchmark():
    """The repository's ``BENCHMARK.json`` with the served cell's entries."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for key, entries in SERVE_ENTRIES.items():
        names = {e["name"] for e in spec[key]}
        spec[key] += [e for e in entries if e["name"] not in names]
    return spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return serve_root(tmp_path_factory.mktemp("serve"))


def _run(root, seed=5, seconds=0.1, trace=False, control=False, log=lambda s: None):
    return run_lib.run_cell(CELL, seed, seconds, trace, root=root, require_chip=False,
                            control=control, log=log)


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_sound_run_is_correct(root, seed, capsys):
    res = _run(root, seed)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["attempted"] == 2 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["particles_compared"]["value"] == 8
    assert res["checks"]["logits_compared"]["value"] == 32
    assert "compiles inside the window: 0 " in capsys.readouterr().out


def test_set_up_is_the_same_work_for_every_seed(root):
    lines = {}
    for seed in (21, 2**33 + 3):
        got = []
        _run(root, seed, log=got.append)
        lines[seed] = [s for s in got if s.startswith("set-up:")]
    assert lines[21] == lines[2**33 + 3] == ["set-up: 2 requests admitted, 8 of 8 slots in use"]


def test_traced_run_reads_every_serve_metric(root):
    res = _run(root, 7, seconds=0.6, trace=True)
    assert res["correct"], res["checks"]
    got = set(res["metrics"])
    # step_mfu.serve needs the chip's peak; it is read below with the table's.
    assert got == set(SERVE_METRICS) - {"step_mfu.serve"}, got
    for name in got:
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["launches_per_tick.serve"]["value"] > 1
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def test_step_mfu_reads_the_trace_with_a_peak(root, monkeypatch):
    from chipbench import trace as trace_lib

    recs = {}
    bench = Bench(root)
    real = bench.driver("serve").run

    def keep(ctx, devices):
        recs["run"] = real(ctx, devices)
        return recs["run"]

    monkeypatch.setattr(Bench, "driver", lambda self, kind: type("D", (), {"run": keep}))
    _run(root, 9, seconds=0.6, trace=True)
    run = recs["run"]
    reduced = trace_lib.load(root / run_lib.OUT_DIR_NAME / "traces" / CELL)
    value = bench.metric("step_mfu.serve").read(
        run, reduced, cell=bench.cell(CELL), peaks=bench.peaks("TPU v5 lite"))
    assert value is not None and value > 0


def test_reference_matches_prefill_then_decode():
    from repro.models.config import ModelConfig
    from repro.models.model import LanguageModel
    from repro.serving.engine import ServeEngine
    from repro.serving.kv_cache import KVCacheConfig

    model = smoke_model()
    cfg = ModelConfig(**model)
    assert cfg.n_heads > cfg.n_kv_heads and cfg.qkv_bias and cfg.tie_embeddings
    params = ref_lib.make_params(model, jax.random.PRNGKey(3))
    assert "unembed" not in params and "bq" in params["blocks"]["attn"]
    eng = ServeEngine(LanguageModel(cfg), params, KVCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, block_size=4,
        max_seqs=2, max_blocks_per_seq=8, num_blocks=32, dtype=cfg.dtype))
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 19), 0, cfg.vocab_size))
    got = [eng.prefill(jnp.asarray(seq[:, :11]), jnp.arange(2, dtype=jnp.int32))]
    for t in range(11, 19):
        got.append(eng.decode(jnp.asarray(seq[:, t:t + 1])))
    got = np.stack([np.asarray(g) for g in got], axis=1)  # [2, 9, V]
    for row in range(2):
        ref = np.asarray(ref_lib.logits(params, model, seq[row], 128))[10:]
        rel = np.linalg.norm(got[row] - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
        assert rel.max() < 1e-5, rel
        last = np.asarray(ref_lib.logits(params, model, seq[row], 128, [18]))
        np.testing.assert_allclose(last[0], ref[-1], rtol=1e-6, atol=1e-6)


def test_control_reads_false(root):
    program, control = _run(root, 11), _run(root, 11, control=True)
    assert program["correct"], program["checks"]
    assert not control["correct"], control["checks"]
    over = [k for k, v in control["checks"].items()
            if isinstance(v["limit"], float) and v["value"] > v["limit"]]
    assert over, control["checks"]


# -- faults planted in the timed path -------------------------------------------


def _patch_engine(monkeypatch, name, wrap):
    from repro.serving.engine import ServeEngine

    real = getattr(ServeEngine, name)
    monkeypatch.setattr(ServeEngine, name, lambda self, *a: wrap(self, real, *a))


def test_missed_copy(root, monkeypatch):
    # After every fork, particle 1 of the request reads the pages of a
    # sibling of another ancestor.  A faulted lineage that the next
    # resampling ends takes the fault with it, and no compared logit
    # changes; on these eight seeds the lineage survives into the
    # compared ticks on 7, 9 and 10.
    def fork_slots(self, real, lo, anc):
        real(self, lo, anc)
        anc = np.asarray(anc)
        other = np.flatnonzero(anc != anc[1])
        if other.size:
            c = self.cache
            self.cache = c._replace(tables=c.tables.at[lo + 1].set(c.tables[lo + int(other[0])]))

    _patch_engine(monkeypatch, "fork_slots", fork_slots)
    wrong = [seed for seed in range(5, 13) if not _run(root, seed)["correct"]]
    assert wrong == [7, 9, 10], wrong


def test_logits_one_tick_stale(root, monkeypatch):
    last = {}

    def decode(self, real, tokens, mask=None):
        out = real(self, tokens, mask)
        prev = last.get(id(self), out)
        last[id(self)] = out
        return prev

    _patch_engine(monkeypatch, "decode", decode)
    assert not _run(root)["correct"]


def test_half_batch_left_out(root, monkeypatch):
    def decode(self, real, tokens, mask=None):
        half = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return real(self, tokens, mask & half)

    _patch_engine(monkeypatch, "decode", decode)
    assert not _run(root)["correct"]


def test_state_unchanged(root, monkeypatch):
    def decode(self, real, tokens, mask=None):
        cache = self.cache
        out = real(self, tokens, mask)
        self.cache = cache
        return out

    _patch_engine(monkeypatch, "decode", decode)
    assert not _run(root)["correct"]


def test_logits_altered_where_produced(root, monkeypatch):
    def decode(self, real, tokens, mask=None):
        return real(self, tokens, mask).at[:, 0].add(1.0)

    _patch_engine(monkeypatch, "decode", decode)
    assert not _run(root)["correct"]


def test_token_fed_other_than_committed(root, monkeypatch):
    # Every row is fed the next token id, while the scheduler commits the
    # sampled one to the request's history.
    def decode(self, real, tokens, mask=None):
        return real(self, (tokens + 1) % self.lm.cfg.vocab_size, mask)

    _patch_engine(monkeypatch, "decode", decode)
    assert not _run(root)["correct"]


# -- the pieces --------------------------------------------------------------


def test_registry_finds_the_cell_driver_and_metrics(root):
    bench = Bench(root)
    cell = bench.cell(CELL)
    assert cell.spec["driver"] == "serve" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "tokens_per_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(SERVE_METRICS)
    assert all(m["moves"] == "tokens_per_s" for m in cell.per_layer)
    assert callable(bench.driver("serve").run)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(bench.metric(m["name"]).read)
    # the rbpf cell does not report the served metrics
    assert "tokens_per_s" not in [m["name"] for m in bench.cell("rbpf.paper").end_to_end]


def test_config_file_states_what_it_runs():
    conf = json.loads(CONFIG.read_text())
    m = conf["model"]
    assert (conf["num_hidden_layers"], conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["intermediate_size"], conf["vocab_size"],
            conf["rope_theta"], conf["rms_norm_eps"], conf["tie_word_embeddings"]) == (
        m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"],
        m["vocab_size"], m["rope_theta"], m["norm_eps"], m["tie_embeddings"])
    assert (m["act"], m["gated_mlp"], m["qkv_bias"]) == (conf["hidden_act"], True, True)
    assert m["param_dtype"] == m["dtype"] == conf["torch_dtype"]
    assert counts.padded_vocab(m) == conf["vocab_size"]  # no padding rows
    entry = next(c for c in serve_benchmark()["configs"] if c["name"] == "qwen2_5_3b")
    assert entry["reduced"] == conf["reduced"] == []
    assert entry["source"] == conf["source"]
    shapes = jax.eval_shape(lambda k: ref_lib.make_params(m, k), jax.random.PRNGKey(0))
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert n_bytes == conf["footprint"]["weights_bytes"]


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_backlog_same_seed_same_requests(seed):
    mix = json.loads((REPO / "chipbench" / "traffic" / "smc_backlog.json").read_text())
    a, b = backlog.requests(mix, seed, 151936), backlog.requests(mix, seed, 151936)
    assert [(r.steps, r.prompt.tobytes()) for r in a] == [(r.steps, r.prompt.tobytes()) for r in b]
    assert len(a) == 96 and all(r.particles == 8 and r.steps == 256 for r in a)
    assert backlog.prompt_lengths(mix) == [256, 384, 512, 1024, 1536, 2048]
    plens = [len(r.prompt) for r in a]
    assert set(plens) <= set(backlog.prompt_lengths(mix)) and len(set(plens)) >= 4
    c = backlog.requests(mix, seed + 1, 151936)
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in c]


def _dot_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2.0 * np.prod(eqn.outvars[0].aval.shape) * np.prod([lhs[d] for d in lc])
        times = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += times * _dot_flops(inner)
                elif hasattr(sub, "eqns"):
                    total += times * _dot_flops(sub)
    return total


def test_decode_flops_match_the_jaxpr():
    from functools import partial

    from repro.models.config import ModelConfig
    from repro.serving import engine as engine_lib
    from repro.serving import kv_cache as kvc
    from repro.serving.kv_cache import KVCacheConfig

    model = smoke_model()
    cfg = ModelConfig(**model)
    rows, blocks, bs = 6, 5, 4
    ccfg = KVCacheConfig(n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                         block_size=bs, max_seqs=rows, max_blocks_per_seq=blocks,
                         num_blocks=16, dtype=cfg.dtype)
    params = jax.eval_shape(lambda k: ref_lib.make_params(model, k), jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(partial(engine_lib._decode_step, cfg, ccfg))(
        params, jax.eval_shape(lambda: kvc.create(ccfg)),
        jax.ShapeDtypeStruct((rows, 1), jnp.int32), jax.ShapeDtypeStruct((rows,), jnp.bool_))
    # The CPU's attention oracle attends over every page of the table.
    want = counts.matmul_flops(model, rows) + counts.attention_flops(model, [blocks * bs] * rows)
    assert _dot_flops(jaxpr.jaxpr) == want
    assert counts.decode_flops(model, [7, 9]) == (counts.matmul_flops(model, 2)
                                                  + counts.attention_flops(model, [7, 9]))


def test_decode_flops_by_hand():
    # Qwen2.5-3B, 36 layers: a row's matrix work is 36 x (2 d (2 x 2048 + 2 x 256)
    # + 6 d f) + 2 d V = 36 x 154,140,672 + 622,329,856 = 6,171,394,048.
    model = json.loads(CONFIG.read_text())["model"]
    assert counts.matmul_flops(model, 1) == 6_171_394_048
    # attention over 100 positions: 36 layers x 4 x 16 heads x 128 x 100
    assert counts.attention_flops(model, [100]) == 29_491_200


def test_weights_come_from_the_key():
    model = smoke_model()
    a = ref_lib.make_params(model, jax.random.PRNGKey(1))
    b = ref_lib.make_params(model, jax.random.PRNGKey(1))
    c = ref_lib.make_params(model, jax.random.PRNGKey(2))
    leaves = list(zip(jax.tree.leaves(a), jax.tree.leaves(b), jax.tree.leaves(c), strict=True))
    assert all(np.array_equal(x, y) and not np.array_equal(x, z) for x, y, z in leaves)
    assert {x.dtype for x, _, _ in leaves} == {np.dtype(model["param_dtype"])}
