"""The benchmark's checks of its configuration files, in the tier-1 run.

``benchmarks/tests/test_cells.py`` holds what the driver refuses before any
run — every configuration file the benchmark has against ``check_config``
and, where its source is a catalog row, ``check_against_source``; every row
of the catalog laid out at the top level; the layout's refusals — but the
driver's tier-1 run reaches ``tests/`` only. These are those cases,
imported, so that a new file's layout is guarded where the driver counts."""

import os
import sys

sys.path[:0] = [
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "tests",
    ),
]

from test_cells import (  # noqa: E402, F401
    test_a_config_holds_its_sources_keys_at_the_top_level,
    test_cell_loads,
    test_config_layout_refusals,
    test_config_refusals,
    test_every_catalog_row_passes_laid_out_and_fails_nested,
    test_every_config_file_passes_the_checks,
    test_every_file_is_named_by_benchmark_json_and_back,
    test_every_reader_loads,
    test_nested_groups_and_lists_are_compared_whole,
    test_the_check_against_the_source_reads_the_top_level,
    test_what_reduced_may_not_name,
)
