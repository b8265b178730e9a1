"""The training entry point's profiler spans and ``--trace-dir``."""
import glob
import sys

from jax.profiler import ProfileData

from repro.launch import train


def test_trace_dir_records_the_operator_spans(tmp_path, monkeypatch, capsys):
    # set after JAX is imported: leaves the persistent cache off here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = tmp_path / "trace"
    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "smollm-360m", "--smoke", "--agents", "2",
        "--local-steps", "1", "--blocks", "3", "--batch", "1", "--seq", "16",
        "--mix", "dense", "--checkpoint", str(tmp_path / "ckpt.npz"),
        "--trace-dir", str(out)])
    train.main()
    assert "block    2" in capsys.readouterr().out
    (path,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name.startswith("train.")]
    # blocks 1 and 2 only: block 0, which compiles, is left out
    for name in ("train.data", "train.step", "train.offload", "train.log"):
        assert spans.count(name) == 2, (name, spans)
    assert spans.count("train.checkpoint") == 1
