"""Tokenized-text pipeline (WikiText-2-shaped) — the C18 equivalent.

Reference: `dataset_preparation.ipynb cell 3:1-61` downloads
WikiText-2-raw-v1, filters empty lines, tokenizes with the GPT-2 fast
tokenizer (pad = eos = 50256, max_length = 128, truncation + padding,
attention masks), and saves an arrow dataset that trainers reload with
`load_from_disk` (`distributed_utils.py:149`).

TPU-native/zero-egress design: three sources behind one interface —
  1. an **arrow reader** (pyarrow over HF-datasets `data-*.arrow` stream
     files) for pre-tokenized corpora on disk,
  2. a **token-file reader** (.npy) for corpora prepared by our own CLI,
  3. a **synthetic generator** (deterministic Zipf-distributed tokens
     with eos padding) so every trainer and benchmark runs on an
     air-gapped machine with realistic shapes and padding statistics.

All arrays are NumPy host-side; sharding onto the mesh happens in
`hyperion_tpu.data.sharding`.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

GPT2_VOCAB_SIZE = 50257  # reference ties the LM to the GPT-2 vocab (distributed_utils.py:80)
GPT2_EOS_ID = 50256      # pad = eos (dataset_preparation.ipynb cell 3)
DEFAULT_SEQ_LEN = 128    # reference tokenization window (cell 3:42)


@dataclasses.dataclass
class TextSplit:
    """One split of a tokenized corpus: [N, seq] ids + mask."""

    input_ids: np.ndarray      # int32 [N, seq]
    attention_mask: np.ndarray  # int8  [N, seq]
    source: str = "synthetic"

    def __post_init__(self):
        assert self.input_ids.shape == self.attention_mask.shape
        self.input_ids = np.ascontiguousarray(self.input_ids, dtype=np.int32)
        self.attention_mask = np.ascontiguousarray(self.attention_mask, dtype=np.int8)

    def __len__(self) -> int:
        return self.input_ids.shape[0]

    @property
    def seq_len(self) -> int:
        return self.input_ids.shape[1]

    def arrays(self) -> dict[str, np.ndarray]:
        return {"input_ids": self.input_ids, "attention_mask": self.attention_mask}

    def verify(self, vocab_size: int = GPT2_VOCAB_SIZE) -> None:
        """Reload-verify step, mirroring the reference's post-save check
        (dataset_preparation.ipynb cell 3:52-61)."""
        if len(self) == 0:
            raise ValueError("empty split")
        if self.input_ids.min() < 0 or self.input_ids.max() >= vocab_size:
            raise ValueError(
                f"token ids outside [0,{vocab_size}): "
                f"[{self.input_ids.min()}, {self.input_ids.max()}]"
            )
        if not np.isin(self.attention_mask, (0, 1)).all():
            raise ValueError("attention mask must be 0/1")
        # mask must be a prefix of ones (right-padding), per the
        # reference's truncation+padding tokenization
        diffs = np.diff(self.attention_mask.astype(np.int8), axis=1)
        if (diffs > 0).any():
            raise ValueError("attention mask is not right-padded")


def load_arrow_split(split_dir: str | Path) -> TextSplit:
    """Read a HF-datasets arrow split directory (data-*.arrow stream
    files with `input_ids` / `attention_mask` list columns) without the
    `datasets` library — pyarrow handles the IPC stream format."""
    import pyarrow as pa
    import pyarrow.ipc as ipc

    split_dir = Path(split_dir)
    files = sorted(split_dir.glob("data-*.arrow"))
    if not files:
        raise FileNotFoundError(f"no data-*.arrow under {split_dir}")
    tables = []
    for f in files:
        with pa.memory_map(str(f)) as src:
            tables.append(ipc.open_stream(src).read_all())
    table = pa.concat_tables(tables)

    def column(name: str, dtype) -> np.ndarray:
        col = table[name].combine_chunks()
        lengths = np.diff(col.offsets.to_numpy())
        flat = col.flatten().to_numpy(zero_copy_only=False)
        if lengths.size and (lengths == lengths[0]).all():
            # fixed seq_len (the reference tokenizes with padding to 128):
            # near-zero-copy reshape instead of to_pylist round-trip
            return flat.reshape(len(lengths), lengths[0]).astype(dtype)
        # ragged rows: one vectorized mask scatter instead of a per-row
        # Python copy loop. Row i's valid slots are the first lengths[i]
        # columns; boolean-mask assignment fills them in C row-major
        # order, which is exactly the order `flat` concatenates the rows
        # in — byte-identical to the old loop, O(rows) Python -> O(1).
        width = int(lengths.max())
        out = np.zeros((len(lengths), width), dtype)
        out[np.arange(width)[None, :] < lengths[:, None]] = flat
        return out

    ids = column("input_ids", np.int32)
    mask = column("attention_mask", np.int8)
    return TextSplit(ids, mask, source=f"arrow:{split_dir}")


def synthetic_lm_split(
    n_examples: int,
    seq_len: int = DEFAULT_SEQ_LEN,
    vocab_size: int = GPT2_VOCAB_SIZE,
    seed: int = 0,
    eos_id: int = GPT2_EOS_ID,
) -> TextSplit:
    """Deterministic WikiText-shaped synthetic corpus.

    Token ids follow a Zipf-like rank distribution (natural text is
    heavy-headed; uniform tokens would make loss curves meaningless) and
    each example gets a random true length with eos right-padding, so
    padding statistics resemble the reference's tokenized corpus.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    probs = 1.0 / ranks ** 1.1
    probs /= probs.sum()
    # Inverse-CDF sampling on the Zipf cumsum: one uniform block + one
    # searchsorted, skipping `rng.choice(p=...)`'s per-call O(vocab)
    # validation/copy overhead. The draw is BIT-IDENTICAL to the old
    # `rng.choice(vocab_size - 1, size, p=probs)` — numpy's Generator
    # builds exactly this renormalized cdf and searches it `side=
    # "right"` against one `rng.random(size)` block internally — so the
    # seed -> corpus mapping (and every fixture downstream) is stable.
    cdf = probs.cumsum()
    cdf /= cdf[-1]
    ids = cdf.searchsorted(
        rng.random((n_examples, seq_len)), side="right"
    ).astype(np.int32)
    lengths = rng.integers(seq_len // 4, seq_len + 1, size=n_examples)
    mask = (np.arange(seq_len)[None, :] < lengths[:, None])
    ids = np.where(mask, ids, eos_id).astype(np.int32)
    return TextSplit(ids, mask.astype(np.int8), source="synthetic")


def save_token_file(split: TextSplit, path: str | Path) -> None:
    np.savez_compressed(path, input_ids=split.input_ids, attention_mask=split.attention_mask)


def load_token_file(path: str | Path) -> TextSplit:
    with np.load(path) as z:
        return TextSplit(z["input_ids"], z["attention_mask"], source=f"npz:{path}")


def load_recordio_split(base: str | Path, split: str) -> TextSplit:
    """Read a split written by `data.prepare` — ids and mask as native
    recordio files (memory-mapped C++ reader, SURVEY §2.3 Arrow row)."""
    from hyperion_tpu.data.recordio import RecordFile

    base = Path(base)
    with RecordFile(base / f"{split}.ids.rio") as ids_f, \
         RecordFile(base / f"{split}.mask.rio") as mask_f:
        ids = ids_f.read_all()
        mask = mask_f.read_all()
    return TextSplit(ids, mask, source=f"recordio:{base / split}")


def load_wikitext2(
    base_dir: str | Path = "data",
    splits: tuple[str, ...] = ("train", "validation"),
    synthetic_sizes: dict[str, int] | None = None,
    seq_len: int = DEFAULT_SEQ_LEN,
    seed: int = 0,
) -> dict[str, TextSplit]:
    """Load the tokenized corpus, preferring on-disk data and falling
    back per-split to synthetic. Search order per split:
    `{base}/wikitext2_tokenized/{split}.ids.rio` (native recordio, the
    `data.prepare` output), `{split}/` (HF arrow dir), `{split}.npz`,
    synthetic.

    Synthetic default sizes follow the reference's post-filter split
    sizes (36718/3760/4358 — SURVEY C18), scaled down 8x so CPU test
    runs stay fast; pass `synthetic_sizes` to override.
    """
    from hyperion_tpu.utils.retry import IO_RETRY, fault_point, retry_call

    def _read(fn):
        """Dataset reads ride the IO retry/backoff: a transient storage
        fault (NFS failover — or a chaos `io_fail` plan)
        backs off and retries instead of crashing the epoch; truly
        corrupt bytes (ValueError from verify/parse) surface at once."""

        def _go():
            fault_point("data_read")
            return fn()

        return retry_call(_go, policy=IO_RETRY)

    base = Path(base_dir) / "wikitext2_tokenized"
    sizes = {"train": 4590, "validation": 470, "test": 545}
    if synthetic_sizes:
        sizes.update(synthetic_sizes)
    out: dict[str, TextSplit] = {}
    for i, split in enumerate(splits):
        arrow_dir = base / split
        npz = base / f"{split}.npz"
        s = None
        if (base / f"{split}.ids.rio").exists():
            try:  # half-written prepare output falls through, like every
                s = _read(lambda: load_recordio_split(base, split))  # other source
            except (OSError, ValueError, KeyError) as e:
                # ValueError/KeyError: truncated or field-less JSON sidecar
                print(f"[load_wikitext2] recordio {split} unreadable "
                      f"({e}); falling back")
        if s is not None:
            pass
        elif arrow_dir.is_dir() and list(arrow_dir.glob("data-*.arrow")):
            s = _read(lambda: load_arrow_split(arrow_dir))
        elif npz.exists():
            s = _read(lambda: load_token_file(npz))
        else:
            s = synthetic_lm_split(sizes.get(split, 512), seq_len=seq_len, seed=seed + i)
        s.verify()
        out[split] = s
    return out
