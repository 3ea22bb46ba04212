"""The drivers, readers and ``run.measure`` as functions at a tiny size on
the CPU (four virtual devices for the data-parallel job): a whole run
minus the look for a chip.  What these runs time means nothing and is
asserted nowhere; they show control flow, counts and the decision
``correct``.

Test-size limits.  At these sizes on the CPU the program computes in
float32 (serve) or bf16 autocast (train), and the readings were (PR 23,
seeds 2147483900 and 5): serve ``served_logit_gap_max`` and ``_mean`` 0
for the program; train ``first_grad_norm_gap`` 0.0035 and
``param_change_norm_gap`` 0.0038 for the program.  The chip-size limits, with their readings, are in the
cell files and PERF.md.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest
from bench_util import ROOT, copy_data_tree, measure, tiny_cell

SERVE_LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
                "malformed_results": 0, "unchecked": 0}
TRAIN_LIMITS = {"loss_gap": 5e-4, "first_grad_norm_gap": 0.02,
                "param_change_norm_gap": 0.02}


@pytest.fixture(autouse=True)
def _amp_off():
    """A training session switches autocast on for the whole process
    (the device's graph flag: conftest.py)."""
    yield
    from singa_tpu import amp

    amp.enable(False)


@pytest.mark.parametrize("name", ["gpt2l-serve-chat", "gpt2l-serve-longdoc"])
def test_serve_cells_run_and_come_out_correct(name):
    line = measure(tiny_cell(name), SERVE_LIMITS)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    names = set(line["metrics"])
    assert {"setup_s", "token_gap_p95_ms"} <= names
    assert ("serve_tokens_per_s" in names) == (name == "gpt2l-serve-longdoc")
    assert ("ttft_mean_ms" in names) == (name == "gpt2l-serve-chat")
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


def test_serve_counts_processed_tokens_at_the_window_edges():
    from benchmark.harness import loader

    cell = tiny_cell("gpt2l-serve-longdoc")
    cell["cell"]["limits"] = SERVE_LIMITS
    import jax

    driver = loader.load_module("drivers", cell["traffic"]["kind"])
    s = driver.Session(cell, jax.devices()[:1])
    run = s.run(11, 1.5, False)
    c = run["counters"]
    block = cell["config"]["engine"]["block_size"]
    assert c["prompt_tokens"] % block == 0 and c["prompt_tokens"] > 0
    assert c["processed_tokens"] == c["prompt_tokens"] + c["generated_tokens"]
    assert c["generated_tokens"] >= len(run["samples"]["token_gap_ms"])
    assert c["compiles_in_window"] == 0
    assert 0 < c["live_slots_mean"] <= c["max_slots"]
    assert 0 < c["blocks_used_peak"] <= c["num_blocks"]
    assert s.check(11, run)["served_logit_gap_max"] <= 1e-4


def test_a_closed_loop_whose_callers_ran_dry_gives_no_result():
    """One request a caller: every chain has ended long before the window
    does, and what is left of the window measures an idle engine.  The
    run raises, and says which key of which file to lengthen."""
    cell = tiny_cell("gpt2l-serve-longdoc")
    cell["traffic"] = dict(cell["traffic"], requests_per_client=1, rounds=1)
    with pytest.raises(RuntimeError, match="closed loop ran dry") as e:
        measure(cell, SERVE_LIMITS)
    said = str(e.value)
    assert "lengthen `rounds` in benchmark/traffic/longdoc.json" in said
    assert "4 of 4 callers" in said and "4 of 4 requests sent" in said
    assert re.search(r"caller \d finished the last of its 1 requests "
                     r"\d\.\d s before the window's end", said)


def test_a_closed_loop_with_chains_to_spare_says_how_far_they_got(capsys):
    line = measure(tiny_cell("gpt2l-serve-longdoc"), SERVE_LIMITS)
    assert line["correct"] is True
    sent, of, furthest, each = map(int, re.search(
        r"window: closed loop, (\d+) of (\d+) requests sent; the caller "
        r"furthest along had sent (\d+) of its (\d+)\n",
        capsys.readouterr().out).groups())
    assert (of, each) == (1024, 256)
    assert line["attempted"] < sent < of and sent / 4 <= furthest < each


class _Log:
    def __init__(self, submitted, token_times, max_new=2):
        self.submitted, self.token_times = submitted, token_times
        self.finished = len(token_times) >= max_new


@pytest.mark.parametrize("last, dry", [
    ([7.0, 7.5], True),          # finished 2.5 s before the window's end
    ([9.0, 10.0], False),        # its last token falls on the edge
    ([9.9, 10.4], False),        # finished in the drain: late, not dry
    ([9.9], False),              # still being served
    ([], False)], ids=["before", "on-the-edge", "in-the-drain", "running",
                       "unsent"])
def test_a_caller_is_dry_once_its_last_request_finished_in_the_window(
        last, dry):
    """``chains_at`` on a fake clock: two callers of two requests, window
    end at 10.0; caller 0 has requests to spare, caller 1's last request
    is the case."""
    from benchmark.drivers import serve
    from benchmark.harness.traffic import Request

    reqs = [Request(0.0, None, 2, client=i % 2, after=i - 2)
            for i in range(4)]
    logs = [_Log(1.0, [2.0, 3.0]), _Log(1.5, [2.5, 4.0]),
            _Log(3.0, [9.5]), _Log(4.0 if last else None, last)]
    cell = {"name": "gpt2l-serve-longdoc"}
    if not dry:
        assert serve.chains_at(cell, reqs, logs, 10.0) == (
            f"closed loop, {4 if last else 3} of 4 requests sent; the "
            f"caller furthest along had sent 2 of its 2")
        return
    with pytest.raises(serve.RanDry, match=(
            r"1 of 2 callers .*caller 1 finished the last of its 2 requests "
            r"2\.5 s before .*4 of 4 requests sent.*lengthen `rounds` in "
            r"benchmark/traffic/longdoc\.json")):
        serve.chains_at(cell, reqs, logs, 10.0)


def test_serve_with_a_token_altered_where_it_is_produced_is_not_correct():
    def tamper(eng):
        emit = eng._emit

        def altered(idx, slot, token, now):
            emit(idx, slot, (int(token) + 1) % 256, now)
        eng._emit = altered

    line = measure(tiny_cell("gpt2l-serve-chat"), SERVE_LIMITS, tamper=tamper)
    assert line["correct"] is False


MID = dict(vocab_size=8192, n_positions=128, n_embd=256, n_layer=6,
           n_head=4, n_inner=1024, layer_norm_epsilon=1e-5)


def test_serve_control_one_precision_down_fails_the_limit():
    """The control: the reference in int8 in the program's place.  The
    control needs no decoding: at each position of a sequence, the token
    that int8 puts first takes the served token's place.  At the drivers'
    tiny test size no int8 rounding flips a first place (measured: gap 0
    on every seed), so this reads the control at a middle size the
    reference alone can hold in a test run (6 layers, 256 wide, 8192
    tokens); there the float32 program's gap is 0 by construction."""
    from benchmark.harness import loader

    ref = loader.load_module("references", "gpt2")
    sizes = ref.sizes_of(MID)
    means = []
    for seed in (2147483900, 5, 6):
        w = ref.init_weights(sizes, seed)
        toks = np.random.default_rng(seed).integers(0, 8192, 120)
        worst, total, _ = ref.served_token_gap(w, sizes, toks, 20, "int8")
        sound = ref.served_token_gap(
            w, sizes, np.concatenate([toks[:20], _greedy(ref, w, sizes,
                                                         toks[:20], 12)]),
            20, "f32")
        assert sound[0] == 0.0
        means.append(total / 100)
        assert worst > SERVE_LIMITS["served_logit_gap_max"]
    assert min(means) > 3 * SERVE_LIMITS["served_logit_gap_mean"], means


def _greedy(ref, w, sizes, prompt, n):
    """n greedy tokens by the float32 reference itself."""
    import jax
    import jax.numpy as jnp

    toks = list(prompt)
    for _ in range(n):
        ids = np.zeros(sizes["P"], np.int32)
        ids[:len(toks)] = toks
        with jax.default_matmul_precision("highest"):
            lg = ref.logits(w, jnp.asarray(ids), sizes["H"], sizes["eps"])
        toks.append(int(np.asarray(lg[len(toks) - 1]).argmax()))
    return np.asarray(toks[len(prompt):], np.int32)


@pytest.mark.parametrize("name", ["gpt2s-train-1chip", "gpt2s-train-dp4"])
def test_train_cells_run_and_come_out_correct(name):
    line = measure(tiny_cell(name), TRAIN_LIMITS, seconds=1.0)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["device"]["count"] == (4 if name.endswith("dp4") else 1)


def test_train_step_that_returns_its_state_unchanged_is_not_correct():
    import jax.numpy as jnp

    def tamper(m):
        def step(ids, labels):
            saved = {k: jnp.copy(t.data) for k, t in m.get_states().items()}
            out = m(ids, labels)
            for k, t in m.get_states().items():
                t.data = saved[k]
            return out
        return step

    line = measure(tiny_cell("gpt2s-train-1chip"), TRAIN_LIMITS, seconds=0.5,
                   tamper=tamper)
    assert line["correct"] is False


def test_train_that_leaves_out_part_of_the_batch_is_not_correct():
    from singa_tpu import tensor

    def tamper(m):
        def step(ids, labels):
            lab = np.array(labels.data)
            lab[1:] = -1                      # only row 0 counts
            return m(ids, tensor.from_numpy(lab, ids.device))
        return step

    line = measure(tiny_cell("gpt2s-train-1chip"), TRAIN_LIMITS, seconds=0.5,
                   tamper=tamper)
    assert line["correct"] is False


def test_dp4_with_one_rank_left_out_of_the_all_reduce_is_not_correct(capsys):
    """The fault the four-chip cell exists to catch: DistOpt's gradient
    mean taken over three of the four ranks' gradients.  Every rank still
    trains and the loss still falls; only the numbers held to dp4's own
    limits see it."""
    from jax import lax

    def tamper(m):
        comm = m.optimizer.communicator

        def drop(a):
            try:
                rank = lax.axis_index(comm.axis_name)
            except NameError:       # the shape probe, outside the step
                return a
            return a * (rank != 0).astype(a.dtype)

        one, fused = comm.all_reduce, comm.fused_synch
        comm.all_reduce = lambda a, average=False: one(drop(a), average)
        comm.fused_synch = lambda arrs, average=False: fused(
            [drop(a) for a in arrs], average)
        return m

    cell = tiny_cell("gpt2s-train-dp4")
    chip_limits = dict(cell["cell"]["limits"])
    line = measure(cell, TRAIN_LIMITS, seconds=0.5, tamper=tamper)
    assert line["correct"] is False
    # a quarter of the gradient is gone: both norms read some 15-30% off,
    # far outside the cell's chip-size limits too (which are dp4's own,
    # not the one-chip cell's: 0.17 would have let this through)
    read = dict(re.findall(r"check: (\w+) = (\S+) \(limit",
                           capsys.readouterr().out))
    for k in ("first_grad_norm_gap", "param_change_norm_gap"):
        assert float(read[k]) > 10 * chip_limits[k], (k, read[k])
        assert chip_limits[k] < 0.01


def test_train_control_one_precision_down_fails_a_limit():
    """The control: the reference in fp8 in the program's place, through
    the same comparison.  At the middle size of the serve control (the
    tiny test size leaves too few values a tensor for fp8 to show) its
    first gradient and its loss lie outside the test-size limits."""
    from benchmark.harness import loader

    job = dict(loader.load_cell("gpt2s-train-1chip")["traffic"],
               rows_per_chip=2, seq_len=64)
    cell = {"config": dict(MID, family="gpt2"), "traffic": job, "chips": 1}
    driver = loader.load_module("drivers", "train_job")
    for seed in (3, 4, 2147483900):
        control = driver.control(cell, seed, "fp8")
        assert (control["first_grad_norm_gap"]
                > TRAIN_LIMITS["first_grad_norm_gap"]
                or control["loss_gap"] > TRAIN_LIMITS["loss_gap"]), control
        same = driver.control(cell, seed, "f32")
        assert max(same.values()) == 0.0


def test_a_traced_run_on_the_cpu_reports_no_device_metric():
    """Nothing from a CPU run is printed under a device metric's name: the
    peaks table has no 'cpu', so the traced run ends with an error and no
    result line."""
    with pytest.raises(KeyError, match="no published peaks"):
        measure(tiny_cell("gpt2s-train-1chip"), TRAIN_LIMITS, seconds=0.3,
                trace=True)


def test_run_py_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2s-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_adding_a_prefix_cell_takes_files_only(tmp_path):
    """The rehearsal PERF.md records: ``gpt2l-serve-prefix`` arrives as one
    traffic file, one cell file and one BENCHMARK.json entry -- the
    configuration, run.py, the harness, drivers and readers untouched."""
    from benchmark.harness import loader

    root = copy_data_tree(str(tmp_path))
    with open(os.path.join(root, "benchmark/traffic/prefix-sessions.json"),
              "w") as f:
        json.dump({"kind": "serve",
                   "arrivals": {"process": "poisson", "rate_per_s": 2.0},
                   "sessions": {"system_prompt_len": 384, "turns_min": 3,
                                "turns_max": 5, "think_s": 1.0},
                   "prompt_len": {"dist": "uniform", "min": 32, "max": 256},
                   "reply_len": {"dist": "uniform", "min": 16, "max": 128},
                   "schedule_seed": 1, "preroll_s": 15}, f)
    # its limits start as chat's (the same configuration and check)
    shutil.copy(
        os.path.join(root, "benchmark/cells/gpt2l-serve-chat.json"),
        os.path.join(root, "benchmark/cells/gpt2l-serve-prefix.json"))
    man = json.load(open(os.path.join(root, "BENCHMARK.json")))
    man["workloads"].append({
        "name": "gpt2l-serve-prefix", "config": "gpt2-large",
        "traffic": "prefix-sessions", "chips": 1,
        "why": "sessions sharing a 384-token system prompt"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "gpt2l-serve-chat" in m.get("workloads", []):
            m["workloads"].append("gpt2l-serve-prefix")
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = tiny_cell("gpt2l-serve-prefix", root=root)
    assert cell["traffic"]["sessions"]["turns_max"] == 5
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_mean_ms", "token_gap_p95_ms", "setup_s"}
    assert "ttft_p90_ms" in {m["name"] for m in cell["per_layer"]}
    line = measure(cell, SERVE_LIMITS)
    assert line["correct"] is True and line["attempted"] > 0
    assert "token_gap_p95_ms" in line["metrics"]
    with pytest.raises(loader.UnknownName):
        loader.load_cell("gpt2l-serve-prefix")     # the repo has no such cell
