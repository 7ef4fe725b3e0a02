import pytest

from hyperion_tpu.config import Config, default_config


class TestConfig:
    def test_roundtrip(self, tmp_path):
        cfg = default_config()
        cfg.train.epochs = 7
        cfg.distributed.fsdp = 4
        p = tmp_path / "config.json"
        cfg.save(p)
        loaded = Config.load(p)
        assert loaded.train.epochs == 7
        assert loaded.distributed.fsdp == 4
        assert loaded.optimization.precision == "bf16"

    def test_mesh_spec_bridge(self):
        cfg = default_config()
        cfg.distributed.fsdp = 2
        assert cfg.distributed.mesh_spec().resolve(8).shape == (4, 2, 1, 1, 1, 1)

    def test_override_dotted(self):
        cfg = default_config().override(**{"train.learning_rate": 1e-3, "optimization.remat": "dots"})
        assert cfg.train.learning_rate == 1e-3
        assert cfg.optimization.remat == "dots"
        # original untouched
        assert default_config().optimization.remat == "none"

    def test_override_unknown_raises(self):
        with pytest.raises(AttributeError):
            default_config().override(**{"train.bogus": 1})

    def test_unknown_keys_ignored_on_load(self):
        cfg = Config.from_dict({"train": {"epochs": 2, "legacy_field": True}})
        assert cfg.train.epochs == 2


class TestCliDataFlags:
    """--train-split / --data_dir plumbing (round-5 real-data runs)."""

    def test_defaults(self):
        from hyperion_tpu.cli.main import build_parser, make_config

        args = build_parser().parse_args(["--model", "language_ddp"])
        cfg = make_config(args, "language_ddp")
        assert cfg.train.train_split == "train"
        assert cfg.train.data_dir == ""

    def test_real_data_invocation(self):
        # a real-data invocation: outputs under base_dir,
        # corpora from data_dir, training on the real test arrow
        from hyperion_tpu.cli.main import build_parser, make_config

        args = build_parser().parse_args([
            "--model", "language_ddp", "--train-split", "test",
            "--data_dir", "data", "--base_dir", "results/tpu_runs",
        ])
        cfg = make_config(args, "language_ddp")
        assert cfg.train.train_split == "test"
        assert cfg.train.data_dir == "data"
        assert cfg.train.base_dir == "results/tpu_runs"
