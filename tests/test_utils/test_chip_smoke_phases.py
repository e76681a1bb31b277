"""chip_smoke.py's three phases at micro size, through the same functions the
chip run calls (see tests/test_chip_smoke.py for the rest of the rehearsal and
for why this file sorts last)."""

import importlib.util
import os
import sys
import warnings

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

OK_LINE = '"ok": true'


def _own_deprecations(caught):
    package = os.path.join(REPO, "sheeprl_tpu") + os.sep
    return [
        str(w.message)
        for w in caught
        if issubclass(w.category, DeprecationWarning) and os.path.abspath(w.filename).startswith(package)
    ]


def test_three_phases_run_at_micro_size_without_deprecation_warnings(tmp_path, capsys):
    out_dir = str(tmp_path)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        # Import-time warnings fire once per process: execute the two modules
        # that used the deprecated shard_map home afresh, under private names.
        for name in ("core/fused_loop.py", "parallel/ring_attention.py"):
            spec = importlib.util.spec_from_file_location(
                "fresh_" + os.path.basename(name)[:-3], os.path.join(REPO, "sheeprl_tpu", name)
            )
            spec.loader.exec_module(importlib.util.module_from_spec(spec))
        checkpoint = chip_smoke.phase_trainer(out_dir, "cpu", chip_smoke.MICRO)
        chip_smoke.phase_fused_lane(out_dir, "cpu", chip_smoke.MICRO)
        chip_smoke.phase_server(checkpoint, out_dir, "cpu", chip_smoke.MICRO)
    assert _own_deprecations(caught) == []
    out = capsys.readouterr().out
    for phase in ("phase A:", "phase B:", "phase C:"):
        assert phase in out
    # The phases report; only main() may print the result line.
    assert OK_LINE not in out
    # What main() prunes before the chip tool copies the directory back.
    chip_smoke.prune_heavy_files(out_dir)
    assert not os.path.exists(checkpoint)
