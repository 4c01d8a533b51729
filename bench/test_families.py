"""CPU tests of the model families (``families/<family>.py``): the dense
families reproduce, bit for bit, what the benchmark drew, computed and
counted before they were modules of their own; a family is added as
files only, and a configuration that names an absent one is refused.

``testdata/family_digests.json`` holds SHA-256 digests of the published
and program weights, the reference's logits and gaps, the int8 control's
weights and gaps, and the lowered text of the weight draw, the published
draw and the gap program, at tiny shapes for Llama tied and untied and
for ChatGLM, on two seeds, one past 2**32; and the work counts of those
shapes and of every configuration file. They were recorded with the code
the family modules replaced.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import control as ctl  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


RECORDED = _json("testdata", "family_digests.json")
VARIANTS = ("llama-tied", "llama-untied", "chatglm")
SEEDS = (5, 2**33 + 7)
SAMPLING = (0.7, 50, 0.9)


def tiny_conf(variant: str) -> dict:
    """A configuration file of the variant's family at a tiny shape: 4
    query heads over 2 KV heads of 16, 2 layers, vocabulary 256."""
    d, hd, ff, vocab, layers = 64, 16, 128, 256, 2
    if variant.startswith("llama"):
        tied = variant == "llama-tied"
        conf = _json("configs", "granite-8b-l16.json")
        conf.update(hidden_size=d, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=hd, intermediate_size=ff,
                    vocab_size=vocab, num_hidden_layers=layers,
                    rope_theta=10000.0, tie_word_embeddings=tied)
        over = dict(rope_theta=10000.0, tie_embeddings=tied)
    else:
        conf = _json("configs", "chatglm3-6b-l20.json")
        conf.update(hidden_size=d, num_attention_heads=4,
                    multi_query_group_num=2, kv_channels=hd,
                    ffn_hidden_size=ff, padded_vocab_size=vocab,
                    num_layers=layers)
        over = {}
    conf["program"]["overrides"].update(
        num_layers=layers, d_model=d, num_heads=4, num_kv_heads=2,
        head_dim=hd, d_ff=ff, vocab_size=vocab, **over)
    return conf


def _sha(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()


def _text(s: str) -> str:
    return hashlib.sha256(s.encode()).hexdigest()


def _tree(t) -> dict:
    return {jax.tree_util.keystr(p): _sha(a)
            for p, a in jax.tree_util.tree_leaves_with_path(t)}


def arrays(conf: dict, seed: int) -> dict:
    cfg, s = harness.program_config(conf)
    fam = weights.family_of(s)
    key = weights.seed_key(seed)
    w = fam.published(s, key)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, s.vocab, 512),
                         jnp.int32)
    picks = jnp.roll(tokens, -1)
    logits = jax.jit(fam.logits, static_argnums=0)(s, w, tokens)
    best, floor = reference._gaps(s, w, tokens, picks, SAMPLING)
    topo = harness.topology(conf)
    w8 = ctl.int8_weights(s, w)
    c_best, c_floor = ctl.gaps(s, w, w8, np.asarray(tokens[:300]),
                               list(np.asarray(tokens[300:360])), SAMPLING,
                               jax.random.key(3))
    return {
        "control": {"int8": _tree(w8), "gaps": [_sha(c_best), _sha(c_floor)]},
        "published": _tree(w), "program": _tree(fam.to_program(s, w)),
        "logits": _sha(logits), "gaps": [_sha(best), _sha(floor)],
        "weights_hlo": _text(harness.weights_program(cfg, s, topo).lower(
            key).as_text()),
        "published_hlo": _text(harness.published_program(s, topo).lower(
            key).as_text()),
        "gaps_hlo": _text(reference._gaps.lower(
            s, w, tokens, picks, SAMPLING).as_text()),
    }


def counts(s) -> dict:
    fam = weights.family_of(s)
    return {
        "prefill_flops": [fam.prefill_flops(s, p) for p in (1, 2, 700, 3584)],
        "decode_flops": [fam.decode_flops(s, c) for c in (1, 129, 4096)],
        "decode_bytes": [fam.decode_bytes(s, t, [c], 8.0)
                         for t, c in ((1, 1), (817, 123457), (1389, 9876543))],
    }


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_weights_reference_and_control_are_those_recorded(variant, seed):
    got = arrays(tiny_conf(variant), seed)
    want = RECORDED["arrays"][f"{variant}/{seed}"]
    for k in want:
        assert got[k] == want[k], k


@pytest.mark.parametrize("name", VARIANTS + (
    "granite-8b-l16", "chatglm3-6b-l20", "granite-8b-tp4"))
def test_work_counts_are_those_recorded(name):
    conf = (tiny_conf(name) if name in VARIANTS
            else _json("configs", name + ".json"))
    s = weights.shapes(conf)
    assert counts(s) == RECORDED["counts"][name]
    # the harness's counts go through ``flops`` to the same functions
    assert flops.decode_bytes(s, 817, [123457], 8.0) == \
        RECORDED["counts"][name]["decode_bytes"][1]


# --- a family added as files only --------------------------------------------


TOY = os.path.join(BENCH, "testdata", "toymoe")
TOY_CELL = "toymoe-tiny.chat-mini"

#: run in a child process from the copy's own ``bench/``: find the cell,
#: optionally alter the program's router to route each token to its top
#: expert alone, run a 1.5 s window and print what the check read
DRIVE = """
import dataclasses, json, sys, time
root, fault, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
sys.path.insert(0, root + "/bench")
import harness
from repro.configs import get_config
cell = harness.find_cell("%s", root)
cfg, s = harness.program_config(cell.conf)
reduced = dataclasses.replace(get_config("grok-1-314b").reduced(),
                              dtype="bfloat16")
if fault == "router":
    import repro.models.blocks as blocks
    real = blocks.apply_moe
    def top1(cfg, p, x, **k):
        return real(dataclasses.replace(cfg, experts_per_token=1), p, x, **k)
    blocks.apply_moe = top1
r = harness.run_cell(cell, seed, 1.5, False, t_start=time.perf_counter(),
                     peak=None, log=lambda m: None)
print(json.dumps({"correct": r["correct"], "failed": r["failed"],
                  "attempted": r["attempted"], "checks": r["checks"],
                  "family": type(s).__module__, "reduced": cfg == reduced,
                  "e2e": sorted(r["metrics"])}))
""" % TOY_CELL


def _digest_files(root: str) -> dict:
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _toy_checkout(tmp_path) -> tuple:
    """A copy of ``bench/`` and ``BENCHMARK.json`` to which only the toy
    family's files are added, and BENCHMARK.json its entries."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest_files(str(bench))
    added = []
    for d, _, files in os.walk(TOY):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), TOY)
            assert not (bench / rel).exists(), rel
            shutil.copy(os.path.join(d, f), bench / rel)
            added.append(rel)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "toymoe-tiny",
                          "file": "bench/configs/toymoe-tiny.json"})
    bm["workloads"].append({"name": TOY_CELL, "config": "toymoe-tiny",
                            "traffic": "chat-mini", "chips": 1})
    for m in bm["end_to_end"]:
        if m["name"] == "output_tok_per_s":
            m["workloads"].append(TOY_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return before, sorted(added)


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("router", False)])
def test_a_family_added_as_files_only_is_served_and_checked(tmp_path, fault,
                                                            correct):
    """The toy MoE family (grok-1-314b's program at its reduced() widths)
    runs through ``find_cell`` and ``run_cell`` from a copy of ``bench/``
    that gained only new files, and its reference reads the served tokens
    correct; with the program's router cut from its top 2 experts to its
    top 1, not correct. No file of the copy is changed."""
    before, added = _toy_checkout(tmp_path)
    assert added == ["cells/toymoe-tiny.chat-mini.json",
                     "configs/toymoe-tiny.json", "families/toymoe.py",
                     "traffic/chat-mini.json"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(BENCH), "src"))
    p = subprocess.run([sys.executable, "-c", DRIVE, str(tmp_path), fault,
                        str(2**31 + 21)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["family"] == "families.toymoe" and r["reduced"]
    assert r["e2e"] == ["output_tok_per_s", "setup_s"]
    assert r["correct"] is correct and r["failed"] == 0 and r["attempted"]
    c = r["checks"]
    assert sorted(c) == ["mean_logit_gap", "mean_nucleus_gap"]
    if correct:
        assert all(v["value"] <= v["limit"] for v in c.values())
    else:
        assert c["mean_logit_gap"]["value"] > c["mean_logit_gap"]["limit"]
    after = _digest_files(str(tmp_path / "bench"))
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == added


def test_a_configuration_naming_an_absent_family_is_refused():
    conf = tiny_conf("llama-tied")
    conf["family"] = "absent"
    path = re.escape(os.path.join(BENCH, "families", "absent.py"))
    with pytest.raises(SystemExit, match=path):
        harness.program_config(conf)
    with pytest.raises(SystemExit, match=path):
        harness.build(conf, 1, False)
