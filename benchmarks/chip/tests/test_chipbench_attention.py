"""``train_attention_device_ms``: the device time under the program's
``attention`` scope in the trainer's step program alone, read from a
trace's op metadata (``chipbench/scopes.py``)."""

from types import SimpleNamespace

import pytest

from chipbench import metrics_io, scopes
from test_chipbench_hybrid import write_trace

STEP = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint"
EMBED = "jit(refresh_embed)/while/body/closed_call"


def events():
    mark = "jit_bench_window_mark(7)"
    return [
        ("XLA Modules", mark, None, 0, 10),
        ("XLA Modules", mark, None, 1000, 10),
        ("XLA Modules", "jit_train_step(3)", None, 20, 600),
        # the step's attention: a forward, the backward kernels, a layout
        # copy, and the chunked scan's loop with an op of its body
        ("XLA Ops", "%gqa_attention.8",
         "jit(train_step)/jvp()/while/body/closed_call/attention/"
         "jit(gqa_attention)/pallas_call", 100, 40),
        ("XLA Ops", "%gqa_attention.10",
         f"{STEP}/attention/jit(gqa_attention)/pallas_call", 150, 60),
        ("XLA Ops", "%fusion.3",
         f"{STEP}/attention/jit(gqa_attention)/transpose", 210, 10),
        ("XLA Ops", "%while.4", f"{STEP}/attention/while", 300, 100),
        ("XLA Ops", "%fusion.5", f"{STEP}/attention/while/body/exp", 320, 30),
        # the step's other ops
        ("XLA Ops", "%fusion.6", f"{STEP}/ffn/dot_general", 450, 100),
        ("XLA Ops", "%fusion.7", f"{STEP}/bshk,hkd->bsd/dot_general", 560, 20),
        # the refresh embed's attention: the same scope in another program
        ("XLA Modules", "jit_refresh_embed(9)", None, 650, 300),
        ("XLA Ops", "%gqa_attention.2",
         f"{EMBED}/attention/jit(gqa_attention)/pallas_call", 700, 200),
        # before the window: not counted
        ("XLA Ops", "%gqa_attention.9",
         "jit(train_step)/jvp()/attention/pallas_call", 2, 5),
    ]


def read(tmp_path, monkeypatch, planes, trace=True):
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path))
    write_trace(str(tmp_path / "plugins" / "t.xplane.pb"), planes)
    record = SimpleNamespace(trace=object() if trace else None, steps=2)
    return metrics_io.reader("train_attention_device_ms")(record)


def test_counts_the_step_attention_alone(tmp_path, monkeypatch):
    # 40 + 60 + 10 + the loop's 100 (its body's op inside it) over 2 steps
    assert read(tmp_path, monkeypatch, [events()]) == pytest.approx(
        1e3 * 210e-9 / 2)


def test_two_chips_give_the_mean(tmp_path, monkeypatch):
    # the second chip's layout copy takes 40 ns, not 10
    late = [e if e[1] != "%fusion.3" else e[:4] + (40,) for e in events()]
    assert read(tmp_path, monkeypatch, [events(), late]) == pytest.approx(
        1e3 * (210e-9 + 240e-9) / 2 / 2)


@pytest.mark.parametrize("kept", [
    "no attention scope",        # the parent's program
    "the embed's attention",     # the scope in the refresh embed alone
])
def test_nothing_to_read_gives_nothing(tmp_path, monkeypatch, kept):
    ev = events()
    keep = {"no attention scope": ("%fusion.6", "%fusion.7"),
            "the embed's attention": ("%fusion.6", "%gqa_attention.2")}[kept]
    planes = [ev[:3] + [e for e in ev if e[1] in keep]]
    assert read(tmp_path, monkeypatch, planes) is None


def test_an_untraced_run_gives_nothing(tmp_path, monkeypatch):
    assert read(tmp_path, monkeypatch, [events()], trace=False) is None
