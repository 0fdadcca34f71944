"""The one reader of profiler captures, ``benchmark/harness/reduce_trace.py``
(union, self time, busy / idle, exposure, classification by name and kind),
and ``harness/phases.py`` over it, inside tier-1: the cases are IMPORTED from
``benchmark/tests/``, which ``pytest tests/`` does not collect, so what the
benchmark holds its reader to is what this suite holds it to. Made-up traces
and the two reduced captures recorded on the chip; nothing is measured here.

Not taken: ``test_new_entries_come_last_and_the_old_ones_are_as_they_were``,
a pin of ``BENCHMARK.json``'s ``per_layer`` order that later entries outgrew
(ROADMAP B2 a).
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
pytest.register_assert_rewrite("benchmark.tests.test_harness",
                               "benchmark.tests.test_phases")

from benchmark.tests.test_harness import (  # noqa: E402,F401
    test_busy_idle_exposure_and_gaps_on_a_made_up_trace,
    test_hlo_text_to_name_and_kind,
    test_reducer_on_traces_recorded_on_the_chip,
    test_union_subtract_and_self_time)
from benchmark.tests.test_phases import (  # noqa: E402,F401
    test_a_shift_of_the_device_clock_leaves_the_inference_split_alone,
    test_an_admission_free_window_reads_zero_not_none,
    test_every_reader_of_a_cell_reads_the_chip_trace,
    test_groups_partition_the_idle_time_and_the_innermost_span_wins,
    test_inference_groups_leave_prefill_out,
    test_new_entries_meet_the_name_unit_and_source_rules,
    test_nothing_to_read_gives_none_and_does_not_raise,
    test_on_the_parents_chip_traces_there_is_nothing_to_read,
    test_per_step_metrics_of_the_made_up_serve_trace,
    test_phases_on_traces_recorded_on_the_chip)
