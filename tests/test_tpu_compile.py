"""Programs of the main path compiled for a TPU v5e that is described
and not attached, at the sizes a deployment runs them (PR 42): what
the chip's compiler refuses, it refuses here, at no chip time. Nothing
runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU's library, and under
several test workers every worker imports this file. Keep every such
test in THIS file: a second file can go to another worker, whose
fixture then skips.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_hist_200ks_percentile_program_fits_one_chip(one_chip):
    """``hist-200k.percentiles``: 229,376 series x 64 slots x 64 bins
    of float32 resident, 128 groups, 16 buckets, two percentiles. The
    program holds its arguments and nothing of their size beside them
    (the one-hot and the six bfloat16 passes of the exact contraction
    are fused into it)."""
    from opentsdb_tpu.ops.histogram_kernels import (HistogramSpec,
                                                    histogram_percentiles)
    s, p, nb, g, t, q = 229_376, 64, 64, 128, 16, 2
    spec = HistogramSpec(s, p, t, g, nb)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = histogram_percentiles.lower(
        shape((s, p * nb), jnp.float32), shape((s, p), jnp.float32),
        shape((s,), jnp.int32), shape((p,), jnp.int32),
        shape((nb,), jnp.float32), shape((q,), jnp.float32),
        spec=spec).compile()
    memory = compiled.memory_analysis()
    resident = s * p * nb * 4
    assert resident <= memory.argument_size_in_bytes < 1.05 * resident
    assert memory.temp_size_in_bytes < 256 << 20
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes < 8 << 30


def test_rank_p95s_program_selects_and_builds_no_one_hot(one_chip):
    """``fleet-1m.rank-p95`` (PR 44): 1,048,576 x 12 float32 cells,
    112 padded groups, ``p95``. The group stage is the selection's
    loop and no ``sort``; the one-hot of the group label is built
    inside each contraction's fusion, 32 times a program, and is no
    array of its own (``[series x groups]`` in bfloat16 is 235 MB,
    and would be read twice a step if the compiler hoisted it out of
    the loop)."""
    from opentsdb_tpu.ops.pipeline import PipelineSpec, run_pipeline_grid
    s, b, g = 1 << 20, 12, 112
    spec = PipelineSpec(num_series=s, num_buckets=b, num_groups=g,
                        ds_function="avg", agg_name="p95")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = run_pipeline_grid.lower(
        shape((s, b), jnp.float32), shape((s, b), jnp.bool_),
        shape((b,), jnp.int32), shape((s,), jnp.int32),
        (shape((), jnp.float32), shape((), jnp.float32)),
        shape((), jnp.float32), spec=spec).compile()
    text = compiled.as_text()
    assert " sort(" not in text and " while(" in text
    # an instruction outside a fusion's body writes its result to
    # memory: none of them has the one-hot's shape
    fused, stored = False, []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = line.startswith("%fused_computation")
        elif not fused and " = " in line and \
                f"[{s},{g}" in line.split(" = ", 1)[1].split("(")[0]:
            stored.append(line.strip()[:120])
    assert not stored, stored
    assert compiled.memory_analysis().temp_size_in_bytes < 640 << 20


def _reversed_or_padded(text: str, s: int) -> list[str]:
    """The module's ``reverse`` instructions, and its ``pad``
    instructions whose result has the grid's ``s`` rows."""
    found = []
    for line in text.splitlines():
        head, _, rest = line.partition(" = ")
        if not rest:
            continue
        result, _, op = rest.partition(" ")
        if op.startswith("reverse(") or (
                op.startswith("pad(") and str(s) in result):
            found.append(line.strip()[:120])
    return found


def test_wides_program_sweeps_and_reverses_nothing(one_chip):
    """``fleet-1m.wide-groupby`` (PR 49): 1,048,576 x 12 float32
    cells, 112 padded groups, ``sum`` of a counter's rate. The
    nearest-present carries of the rate and of ``fill_gaps`` are a
    sweep along the 12 buckets with every step written out: no
    ``reverse``, no grid-sized ``pad`` (the associative scan's 12 and
    76), no loop, and temporaries of a few grids (460 MB for the
    fill alone before)."""
    from opentsdb_tpu.ops.interp import carry_form
    from opentsdb_tpu.ops.pipeline import PipelineSpec, run_pipeline_grid
    s, b, g = 1 << 20, 12, 112
    assert carry_form(b) == "unrolled"
    spec = PipelineSpec(num_series=s, num_buckets=b, num_groups=g,
                        ds_function="avg", agg_name="sum", rate=True,
                        rate_counter=True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = run_pipeline_grid.lower(
        shape((s, b), jnp.float32), shape((s, b), jnp.bool_),
        shape((b,), jnp.int32), shape((s,), jnp.int32),
        (shape((), jnp.float32), shape((), jnp.float32)),
        shape((), jnp.float32), spec=spec).compile()
    text = compiled.as_text()
    assert not _reversed_or_padded(text, s)
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_month_avgs_program_sweeps_in_two_loops(one_chip):
    """``rollup-100k.month-avg`` (PR 49): two float32 grids of
    114,688 x 768, 128 padded groups, the division and ``sum``. At
    768 buckets the sweep runs as a loop each way (``carry=loop``):
    no ``reverse`` and no grid-sized ``pad`` (12 and 220 before), and
    under 2 GB of temporaries beside the pair of 705 MB (3.85 GB
    before, which took 76-88 s to compile)."""
    from opentsdb_tpu.ops.interp import carry_form
    from opentsdb_tpu.ops.pipeline import (PipelineSpec,
                                           run_pipeline_avg_div)
    s, b, g = 114_688, 768, 128
    assert carry_form(b) == "loop"
    spec = PipelineSpec(num_series=s, num_buckets=b, num_groups=g,
                        ds_function="avg", agg_name="sum")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    # a month of hours passes int32 milliseconds: float32 offsets
    compiled = run_pipeline_avg_div.lower(
        shape((s, b), jnp.float32), shape((s, b), jnp.float32),
        shape((b,), jnp.float32), shape((s,), jnp.int32),
        (shape((), jnp.float32), shape((), jnp.float32)),
        shape((), jnp.float32), spec=spec).compile()
    text = compiled.as_text()
    assert not _reversed_or_padded(text, s)
    assert text.count(" while(") == 2
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 2 * s * b * 4
    assert memory.temp_size_in_bytes < 2 << 30


def test_refreshs_program_assembles_its_columns_in_the_one_module(
        one_chip):
    """``fleet-1m.refresh`` (PR 50): thirteen ``[1,048,576]`` float32
    columns and their masks (eleven resident, two the request's own),
    112 padded groups, ``sum`` of a counter's rate. The module that
    runs the tail puts the ``[1,048,576 x 14]`` grid together and
    hands it back beside the result, laid out as the grid program
    takes it (series on the lanes: the columns are its rows as they
    lie in memory, so no transpose), with the wide program's sweep:
    no ``reverse``, no grid-sized ``pad``, no loop."""
    from opentsdb_tpu.ops.pipeline import (PipelineSpec,
                                           run_pipeline_columns,
                                           run_pipeline_grid)
    s, b, b_pad, g = 1 << 20, 13, 14, 112
    spec = PipelineSpec(num_series=s, num_buckets=b_pad, num_groups=g,
                        ds_function="avg", agg_name="sum", rate=True,
                        rate_counter=True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rest = (shape((b_pad,), jnp.int32), shape((s,), jnp.int32),
            (shape((), jnp.float32), shape((), jnp.float32)),
            shape((), jnp.float32))
    compiled = run_pipeline_columns.lower(
        tuple(shape((s,), jnp.float32) for _ in range(b)),
        tuple(shape((s,), jnp.bool_) for _ in range(b)),
        *rest, spec=spec).compile()
    text = compiled.as_text()
    assert not _reversed_or_padded(text, s)
    assert " while(" not in text and " transpose(" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 256 << 20
    # the grid and its mask come back: 14 buckets on 16 sublanes
    assert s * b_pad * 5 <= memory.output_size_in_bytes \
        < s * 16 * 5 + (1 << 20)
    results = text.split("entry_computation_layout=")[1].split("->")[1]
    grid = run_pipeline_grid.lower(
        shape((s, b_pad), jnp.float32), shape((s, b_pad), jnp.bool_),
        *rest, spec=spec).compile().as_text()
    operands = grid.split("entry_computation_layout={(")[1].split("->")[0]
    for array in ("f32[1048576,14]{0,1:T(8,128)}",
                  "pred[1048576,14]{0,1:T(8,128)(4,1)}"):
        assert array in results and array in operands
