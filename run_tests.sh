#!/bin/bash
# The builder's test entry point. Tests run on the CPU: JAX_PLATFORMS=cpu,
# a virtual 8-device mesh (tests/conftest.py), pallas kernels interpreted.
# The chip is reached through chip_smoke.py and the CST_TPU_TESTS=1 set.
#
#   ./run_tests.sh                  # the fast set: everything not listed
#                                   # in tests/slow_tests.txt
#   ./run_tests.sh --all            # every test
#   ./run_tests.sh <pytest args>    # the fast set with extra arguments
#
# Four sequential pytest processes, by alphabetical ranges of file names
# (a new test file lands in its range by itself). One process cannot run
# the whole suite: each jit compilation leaves memory mappings behind
# (LLVM's JIT code pages are never unmapped in-process), and past
# vm.max_map_count (65530 here) the next XLA CPU compile segfaults
# instead of erroring. The driver's gate runs the same fast set on six
# xdist workers, one file a worker at a time (`-p xdist -n 6 --dist
# loadfile`, 1,470 s), which stays under the ceiling the same way; the
# benchmark's own tests are `python -m pytest cellbench -m "not slow"`
# and belong to neither.

MARK=(-m "not slow")
if [ "$1" = "--all" ]; then
    MARK=(); shift
fi
if [ "$#" -eq 0 ]; then set -- -x -q; fi

# The static-analysis passes first, as a step of their own (stdlib only,
# about a second): hot-path, lock-discipline, dispatch-discipline and
# lifecycle-discipline over the serving modules (docs/analysis.md has
# the catalog and the pragma syntax). The exit code propagates, so a
# finding reads as a broken serving invariant before any pytest output;
# the JSON report is left where ANALYSIS_JSON says, for CI to upload.
ANALYSIS_JSON="${ANALYSIS_JSON:-/tmp/cloud_server_tpu_analysis.json}"
env JAX_PLATFORMS=cpu \
    python -m cloud_server_tpu.analysis --json > "$ANALYSIS_JSON"
arc=$?
if [ "$arc" -ne 0 ]; then
    # surface the findings on the console before failing the gate
    cat "$ANALYSIS_JSON"
    exit $arc
fi

shopt -s nullglob  # an empty group must not reach pytest as a literal
rc=0
# four groups: p-r carries the biggest graphs (paged server, pipeline,
# ring) and with --all it crossed the map ceiling at ~150 tests when
# p-z ran as one process
for group in 'tests/test_[a-f]*.py' 'tests/test_[g-o]*.py' \
             'tests/test_[p-r]*.py' 'tests/test_[s-z]*.py'; do
    files=( $group )
    if [ "${#files[@]}" -eq 0 ]; then
        continue
    fi
    env JAX_PLATFORMS=cpu \
        python -m pytest "${files[@]}" "${MARK[@]}" "$@"
    grc=$?
    # 5 = "no tests collected" (a group can be empty under -m filters)
    if [ "$grc" -ne 0 ] && [ "$grc" -ne 5 ]; then
        rc=$grc
        break
    fi
done
exit $rc
