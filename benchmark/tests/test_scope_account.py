"""``metrics/readers/scope_account.py`` and ``run_span_fun_s.py`` on
hand-written events and a hand-built ``run`` record. Nothing here is a
time of anything: the numbers are the events' own."""

import os

import pytest

from benchmark.harness import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = "benchmark/metrics/readers/"
STEP = "jit(hvd_dp_step)/shard_map/"
GRAD = STEP + "hvd.loss_and_grad/"
BACK = GRAD + "transpose(jvp(NemotronH))/lm.model/"


def _module(path):
    """The reader's module, not only its ``read``."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("reader_under_test",
                                                  os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ACCOUNT = _module(READERS + "scope_account.py")
LISTED = ACCOUNT.program_scopes()

# (instruction, op_name path, start ns, end ns) of one step of one chip
EVENTS = [
    # a leaf, forward; then the same leaf coming back, wrapped by JAX
    ("fusion.1", GRAD + "jvp(NemotronH)/lm.model/head/lm.head/lm_head/"
     "dot_general", 0, 300),
    ("fusion.2", BACK + "head/lm.head/lm_head/dot_general", 300, 900),
    # the loss by rule: under the phase, outside the model
    ("reduce.3", GRAD + "jvp()/reduce_sum", 900, 1000),
    ("gather.4", GRAD + "jvp(jit(take_along_axis))/gather", 1000, 1020),
    ("add.5", GRAD + "transpose(jvp())/add_any", 1020, 1100),
    # a path that repeats itself under jax.checkpoint: the LAST name counts
    ("kernel.6", BACK + "layer_0/mixer/ssm.mixer/hvd.loss_and_grad/"
     "jvp(NemotronH)/lm.model/layer_0/mixer/ssm.mixer/checkpoint/ssm.scan/"
     "jit(_sweep)/pallas_call", 1100, 1500),
    # under a container only: unnamed, grouped after the container
    ("add.7", GRAD + "jvp(NemotronH)/lm.model/layer_3/add", 1500, 1530),
    ("add.8", GRAD + "jvp(NemotronH)/lm.model/layer_5/add", 1530, 1570),
    ("mul.9", BACK + "layer_0/mixer/ssm.mixer/mul", 1570, 1580),
    # under nothing of the list
    ("convert.10", "jit(hvd_dp_step)/convert_element_type", 1580, 1585),
    # an enclosing conditional with no path round its branch's three ops,
    # one of them without a path either
    ("conditional.11", "", 2000, 3000),
    ("fusion.12", GRAD + "jvp(NemotronH)/lm.model/layer_1/moe/"
     "jit(_forward_where_they_fit)/cond/branch_1_fun/moe.experts/mul",
     2000, 2600),
    ("copy.13", None, 2600, 2700),
    ("fusion.14", BACK + "layer_1/hvd.loss_and_grad/jvp(NemotronH)/lm.model/"
     "layer_1/checkpoint/moe/jit(_backward_where_they_fit)/cond/"
     "branch_1_fun/transpose(jvp(moe.combine))/gather", 2700, 2990),
    # a conditional in a conditional: both go, the op inside stays
    ("conditional.15", "", 3000, 3500),
    ("conditional.16", "", 3100, 3400),
    ("fusion.17", STEP + "hvd.optimizer/add", 3150, 3350),
    # nested leaves: the innermost wins; a fusion across a border goes
    # where the first part of its own path says
    ("all-reduce.18", STEP + "hvd.optimizer/hvd.grad_exchange/bucket0/"
     "hvd.wire/psum", 3500, 3900),
    ("fusion.19", BACK + "embed/lm.embed/tok_emb/scatter-add;" + STEP
     + "hvd.optimizer/hvd.grad_exchange/bucket0/pack/reshape", 3900, 3950),
    # the compiler's own, no metadata; a zero-length event encloses nothing
    ("copy-start.20", None, 3950, 3950),
    ("copy-done.21", None, 3950, 4000),
]
WANT_NS = {
    "lm.head": 300 + 600, "lm.loss": 100 + 20 + 80, "ssm.scan": 400,
    "unnamed": 30 + 40 + 10 + 5, "moe.experts": 600, "moe.combine": 290,
    "no_path": 100 + 0 + 50, "hvd.optimizer": 200, "hvd.wire": 400,
    "lm.embed": 50,
}


def test_the_program_has_the_list_the_reader_reads():
    assert LISTED["lm.loss"] == ("rule", "hvd.loss_and_grad")
    assert LISTED["lm.model"][0] == "container"
    assert LISTED["lm.head"][0] == "leaf"


def test_enclosing_events_are_dropped_and_their_branches_counted_once():
    kept = ACCOUNT.without_enclosing(reversed(EVENTS))
    names = [e[0] for e in kept]
    assert not any(n.startswith("conditional") for n in names)
    assert len(kept) == len(EVENTS) - 3
    assert sorted(names) == sorted(
        e[0] for e in EVENTS if not e[0].startswith("cond"))
    assert [e[2] for e in kept] == sorted(e[2] for e in kept)


@pytest.mark.parametrize("part", sorted(WANT_NS))
def test_each_part_on_its_example(part):
    parts, _ = ACCOUNT.account(EVENTS, LISTED)
    assert parts[part] == pytest.approx(WANT_NS[part] * 1e-9)


def test_the_parts_sum_to_the_union_of_the_events():
    from benchmark.harness import trace_reduce
    parts, groups = ACCOUNT.account(EVENTS, LISTED)
    union = trace_reduce._union_seconds((e[2], e[3]) for e in EVENTS)
    # the two conditionals hold 10 + 300 ns in which no op of theirs ran
    assert sum(parts.values()) == pytest.approx(union - 310e-9)
    assert set(parts) == set(WANT_NS)
    assert dict(groups) == pytest.approx({
        "lm.model/layer_N/add": 70e-9, "ssm.mixer/mul": 10e-9,
        "hvd_dp_step/convert_element_type": 5e-9})


def test_read_gives_ms_a_step_and_nothing_without_a_trace_or_a_list(
        monkeypatch):
    ctx = {"trace": object(),
           "_scope_account": ({"lm.loss": 4.5, "lm.head": 30.0}, {})}
    assert ACCOUNT.read(ctx, part="loss") == 4.5
    assert ACCOUNT.read(ctx, part="lm.head") == 30.0
    assert ACCOUNT.read(ctx, part="unnamed") == 0.0
    assert ACCOUNT.read(ctx, part="ssm.scan") is None
    assert ACCOUNT.read({"trace": None}, part="loss") is None
    # the parent of the PR that brought the list has none: nothing to read
    monkeypatch.setattr(ACCOUNT, "program_scopes", lambda: None)
    assert ACCOUNT.read({"trace": object(), "workload": {"name": "x"}},
                        part="unnamed") is None


def test_run_span_fun_s_on_a_hand_built_run_record():
    from horovod_tpu import trace
    read = metrics.load_reader(ROOT, READERS + "run_span_fun_s.py")
    trace.reset()
    assert read({}, span="compile.backend", fun="hvd_dp_step") is None
    run = trace.run_tid()
    step, other = {"fun": "hvd_dp_step"}, {"fun": "_threefry_split"}
    trace.add_span(run, "compile.trace", 10.0, 2.5, args=step)
    trace.add_span(run, "compile.trace", 11.0, 0.125, args=other)
    trace.add_span(run, "compile.lower", 12.5, 1.25, args=step)
    trace.add_span(run, "compile.backend", 14.0, 6.0, args=step)
    trace.add_span(run, "compile.cache_load", 15.0, 5.0,
                   parent="compile.backend", args=step)
    trace.add_span(run, "compile.backend", 30.0, 0.5, args=other)
    trace.add_span(run, "compile.backend", 40.0, 1.0, args=step)  # again
    trace.add_span(run, "init", 0.0, 9.0)
    assert read({}, span="compile.trace", fun="hvd_dp_step") == 2.5
    assert read({}, span="compile.lower", fun="hvd_dp_step") == 1.25
    assert read({}, span="compile.backend", fun="hvd_dp_step") == 7.0
    assert read({}, span="compile.cache_load", fun="hvd_dp_step") == 5.0
    assert read({}, span="compile.lower", fun="_threefry_split") is None
    # compiled afresh: no time loading, where the caller says what proves
    # that the function compiled at all
    assert read({}, span="compile.cache_load", fun="_threefry_split",
                zero_with="compile.backend") == 0.0
    assert read({}, span="compile.cache_load", fun="nobody",
                zero_with="compile.backend") is None
    trace.reset()
