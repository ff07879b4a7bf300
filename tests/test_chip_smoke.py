"""``chip_smoke.py`` on the CPU: its body at a tiny scale with the kernels
interpreted, and its refusal to run anywhere but on a TPU."""

import chip_smoke


def test_smoke_body_matches_numpy_without_fallbacks():
    report = chip_smoke.run_smoke(0.05, n_batches=1, log=lambda *a: None)
    # every jax/pallas result of both rounds equalled numpy's (run_smoke
    # raises on the first mismatch) ...
    n = len(chip_smoke.ROUNDS) * len(chip_smoke.DEVICE_BACKENDS)
    assert report["queries_compared"] == n * chip_smoke.BATCH_QUERIES
    # ... no host fallback fired, and the intersect kernel launched,
    # interpreted as the cpu platform requires
    chip_smoke.verify(report, compiled=False)


def test_smoke_refuses_a_machine_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
