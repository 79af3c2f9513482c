"""Operations and bytes that the work of one update or one search needs,
from shapes alone: what the mathematics asks for, not what an
implementation happens to do. Recomputation (rematerialized layers, the
logits a flash-style backward kernel recomputes) is not counted, nor are
embedding gathers, which are no matrix products.
"""

from __future__ import annotations


def tower_forward_flops(n_seqs: int, seq_len: int, model: dict) -> float:
    """Matrix-product FLOPs of one BERT tower's forward pass over
    ``n_seqs`` sequences of ``seq_len`` tokens: QKV, output, both FFN
    products, and attention's QK^T and AV at that length."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    per_token = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff + 2 * 2 * seq_len * d
    return float(n_seqs * seq_len * model["num_hidden_layers"] * per_token)


def similarity_flops(rows: int, cols: int, d: int) -> float:
    return float(2 * rows * cols * d)


def contaccum_chunk_shapes(wl: dict, d: int):
    """(rows, cols) of one chunk's similarity matrix once the banks are full:
    local queries plus the query bank, against local positives, local hard
    negatives and the passage bank."""
    n_local, bank, n_hard = wl["local_batch"], wl["bank"], wl["n_hard"]
    return n_local + bank, n_local * (1 + n_hard) + bank


def contaccum_update_flops(wl: dict, model: dict) -> float:
    """Matrix-product FLOPs of one ContAccum update (forward and backward,
    so three times the forward): both towers over the whole batch at their
    own sequence lengths, and the similarity matrix of every chunk."""
    b, k = wl["total_batch"], wl["total_batch"] // wl["local_batch"]
    towers = (tower_forward_flops(b, wl["q_len"], model)
              + tower_forward_flops(b * (1 + wl["n_hard"]), wl["p_len"], model))
    rows, cols = contaccum_chunk_shapes(wl, model["hidden_size"])
    return 3.0 * (towers + k * similarity_flops(rows, cols, model["hidden_size"]))


def infonce_calls(wl: dict, model: dict):
    """The loss's softmax-statistics calls of one update, as
    ``[(rows, cols, d, n_calls)]``: per chunk, the local query rows and the
    query-bank rows, each against every column. Each call is a forward and
    a backward (dQ and dP)."""
    d = model["hidden_size"]
    k = wl["total_batch"] // wl["local_batch"]
    _, cols = contaccum_chunk_shapes(wl, d)
    return [(wl["local_batch"], cols, d, k), (wl["bank"], cols, d, k)]


def infonce_least_s(wl: dict, model: dict, least_time) -> float:
    """Least device time of one update's loss calls: each of forward, dQ and
    dP is one (rows x cols x d) product that reads the rows and the
    columns in bf16 at least once."""
    total = 0.0
    for rows, cols, d, n in infonce_calls(wl, model):
        flops = similarity_flops(rows, cols, d)
        bytes_ = 2 * (rows + cols) * d
        total += 3 * n * least_time(flops, bytes_)
    return total


def tower_layer_bytes(model: dict, bytes_per_param: int = 4) -> float:
    """Bytes of one tower's layer weights as stored (fp32 masters)."""
    d, ff, nl = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    per_layer = 4 * d * d + 2 * d * ff + 3 * d + d + ff + d + 4 * d
    return float(nl * per_layer * bytes_per_param)


def search_work(batch: int, q_len: int, rows_per_device: int, model: dict,
                index_itemsize: int = 2):
    """(flops, bytes) per device of one search call: the query tower over
    the batch, the scores of every index row held on the device, and the
    index rows and tower weights read once."""
    d = model["hidden_size"]
    flops = tower_forward_flops(batch, q_len, model) + similarity_flops(batch, rows_per_device, d)
    bytes_ = rows_per_device * d * index_itemsize + tower_layer_bytes(model)
    return flops, bytes_
