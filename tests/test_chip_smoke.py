"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The smoke is the quickest proof that the serving path starts on the chip,
and chip time is the expensive place to find a wrong path, argument or
check in it. These tests run its phases — write the files, start ``cli
serve`` in a child, every request and comparison, SIGTERM — against the
forced-CPU backend (one virtual device, and four for ``--tp 4``).
What they cannot show is anything about the chip: ``main()`` accepts only a
server that reports platform ``tpu``.
"""

import pytest

import chip_smoke

#: smallest shape the q40 kernels and --tp 4 take (kv heads divide by 4,
#: vocab holds the 259 fixed tokenizer pieces)
TINY = dict(dim=256, hidden_dim=512, n_layers=2, n_heads=8, n_kv_heads=4,
            vocab_size=512, seq_len=256)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    out = tmp_path_factory.mktemp("smoke")
    model, tok = chip_smoke.write_files(str(out), TINY, seed=0)
    return model, tok, str(out)


def _visible_devices(monkeypatch, n: int) -> None:
    """The smoke's children see ``n`` virtual devices, as its machine shows
    the server exactly the chips that form of the smoke is for."""
    monkeypatch.setenv("XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")


def test_one_chip_phases_pass_on_cpu(files, monkeypatch):
    model, tok, out = files
    _visible_devices(monkeypatch, 1)
    device = chip_smoke.run_one_chip(model, tok, out, "cpu", 300.0)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_four_chip_phase_passes_on_virtual_devices(files, monkeypatch):
    model, tok, out = files
    _visible_devices(monkeypatch, 4)
    device = chip_smoke.run_four_chips(model, tok, out, "cpu", 4, 300.0)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 4}


def test_reference_refuses_tokens_the_model_would_not_say(files):
    """The tolerance has teeth: ids picked without the model sit several
    logit spreads below the reference's best."""
    model, tok, out = files
    wrong = list(range(chip_smoke.N_FIXED_PIECES, chip_smoke.N_FIXED_PIECES
                       + chip_smoke.MAX_TOKENS))
    with pytest.raises(chip_smoke.SmokeFailure, match="not what this model says"):
        chip_smoke.score(model, tok, out, "cpu",
                         [("made up", chip_smoke.PROMPTS[0], wrong)])


def test_a_server_off_the_tpu_is_refused():
    """The gate behind the last line: ``main()`` asks for platform "tpu",
    and what the serving process reported decides."""
    cpu = {"device": {"platform": "cpu", "kind": "cpu", "count": 1,
                      "bytes_in_use": [None]}}
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'tpu'"):
        chip_smoke.check_device(cpu, "tpu", 1)
    tpu = {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "bytes_in_use": [5 * 2**30]}}
    assert chip_smoke.check_device(tpu, "tpu", 1) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(chip_smoke.SmokeFailure, match="exactly 4"):
        chip_smoke.check_device(tpu, "tpu", 4)  # --chips 4 on a one-chip host
    # the one-chip form where the server saw four: the last line would count
    # devices the --tp 1 engine never used
    four = {"device": dict(tpu["device"], count=4, bytes_in_use=[2**30] * 4)}
    with pytest.raises(chip_smoke.SmokeFailure, match="exactly 1"):
        chip_smoke.check_device(four, "tpu", 1)


def test_a_replicated_or_lopsided_placement_is_refused():
    """``--tp 4`` must shard: the byte counts four v5e reported for the
    4.24 GB file pass, everything on the first device or a full copy on
    each does not, and a TPU without allocator statistics fails."""
    size = 4_240_000_000
    chip_smoke.check_shares([1574782976, 1573628416, 1573628416, 1573628416],
                            size, "tpu")
    chip_smoke.check_shares([None] * 4, size, "cpu")  # the CPU rehearsal
    for held in ([5_200_000_000, 0, 0, 0], [5_200_000_000] * 4, [None] * 4):
        with pytest.raises(chip_smoke.SmokeFailure, match="share of the weights"):
            chip_smoke.check_shares(held, size, "tpu")
