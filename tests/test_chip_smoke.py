"""chip_smoke.py has no CPU mode: without a TPU it fails before any work."""

import pytest

import chip_smoke


def test_without_a_tpu_it_exits_nonzero_naming_the_reason(capsys):
    """``python chip_smoke.py`` is ``sys.exit(main())``; on this platform
    main() must leave through ``sys.exit("... no TPU ...")`` (exit code 1,
    message on stderr) before it imports the library or starts a phase."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert "no TPU" in str(exc.value.code)
    out = capsys.readouterr().out
    assert '"platform": "cpu"' in out   # it said what it found
    assert '"ok"' not in out and "phase" not in out
