"""``correct`` has to come out false when the timed path is broken: the
whole run is driven on the CPU at a tiny size, with the look for a chip
skipped, and the farm under it replaced by the control (the reference in
bfloat16, one precision below the configuration's float32), by a farm
whose work function alters one band's answer where it is produced, and by
a farm that leaves half of its bands out."""

import argparse
import time

import pytest

from bench import drive, run_cell
from bench.control import control_spec
from bench.tests import rig


def _rebuilt(spec, *, work=None, items=None):
    """``spec``'s farm with its work function or its item count changed."""
    from repro.core.dsl import ClusterSpec
    from repro.core.processes import EmitDetails

    emit = spec.host_net.emit.e_details
    if items is not None:
        emit = EmitDetails(name=emit.name, init=emit.init,
                           init_data=(items,), create=emit.create)
    return ClusterSpec.simple(
        host=spec.host, nclusters=spec.nclusters,
        workers_per_node=spec.node_net.group.workers, emit_details=emit,
        work_function=work or spec.node_net.group.function,
        result_details=spec.host_net.collector.r_details)


def altered_answer(config, lines):
    spec = drive.job_spec(config, lines)
    work = spec.node_net.group.function

    def work_altered(band):
        iterations, white, points = work(band)
        return (iterations + (band == 1), white, points)

    return _rebuilt(spec, work=work_altered)


def half_the_bands(config, lines):
    bands = lines // config["lines_per_band"]
    return _rebuilt(drive.job_spec(config, lines), items=max(1, bands // 2))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rig.make_root(tmp_path_factory.mktemp("faults"),
                         [rig.cell("tiny.batch", "batch")])


def _run(root, factory):
    args = argparse.Namespace(workload="tiny.batch", seed=2**33 + 1,
                              seconds=1.0, trace=0)
    return run_cell.run(root, args, t_start=time.monotonic(), platform=None,
                        spec_factory=factory, log=lambda _msg: None)


def test_the_control_in_the_programs_place_is_not_correct(root):
    result = _run(root, control_spec)
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["iteration_gap"]["value"] > checks["iteration_gap"]["limit"]
    assert checks["band_gap"]["value"] == 0  # every band came back


def test_an_answer_altered_where_it_is_produced_is_not_correct(root):
    result = _run(root, altered_answer)
    assert result["correct"] is False
    assert result["checks"]["iteration_gap"]["value"] == 1
    assert result["checks"]["white_gap"]["value"] == 0


def test_half_the_bands_left_out_is_not_correct(root):
    result = _run(root, half_the_bands)
    assert result["correct"] is False
    assert result["checks"]["band_gap"]["value"] == 4  # 8 bands, 4 came
    assert result["failed"] == result["attempted"] >= 1
