"""Bytes and operations the paged decode kernel REQUIRES at two query
positions a row (``ops/paged_attention.py`` at ``T = 2``: a step that
verifies a draft), from the published sizes (``model_type: exaone_moe``
keys) and the engine's page size.

A call serves one pool layer of one decode step.  For each live row it
reads the pages its two queries see (in a global layer every page up to
the row's length; in a window layer from the page that holds the FIRST
query's window edge on) and writes the step's two K/V rows.  A page of
one layer is ``kv_heads * page_size * 2 * head_dim`` bfloat16 values; a
row ``kv_heads * 2 * head_dim``.  (The write-back moves the whole
sublane group that holds a row, 16 positions; what the algorithm needs is
the row.)  The queries and outputs are ``2 * heads * head_dim`` values a
row each way.  The kernel's bound is HBM bandwidth: a page's two
products are ``8 * heads * page_size * head_dim`` operations, a
fifteenth of the time its bytes take on a v5e."""


def page_bytes(cfg: dict, page_size: int, itemsize: int = 2) -> int:
    """One page of one pool layer."""
    return (cfg["num_key_value_heads"] * page_size * 2 * cfg["head_dim"]
            * itemsize)


def row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One token's K/V row in one pool layer."""
    return cfg["num_key_value_heads"] * 2 * cfg["head_dim"] * itemsize


def query_bytes(cfg: dict, itemsize: int = 2) -> int:
    """A live row's two queries in and two outputs out."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * itemsize


def verify_pages(length: int, page_size: int, window: int = None) -> int:
    """Pages ONE call reads for a row whose last query sees ``length``
    positions (its first ``length - 1``): up to ``ceil(length /
    page_size)``, from page 0 in a global layer, from the page of
    position ``length - 1 - window`` (the first query's edge) in a layer
    with a window."""
    first = 0 if window is None else max(0, length - 1 - window) // page_size
    return -(-length // page_size) - first


def verify_bytes(cfg: dict, pages_read: float, rows_written: float,
                 page_size: int) -> float:
    """``pages_read`` pages and ``rows_written`` rows over any number of
    calls (the engine's ``decode_pages_read`` and ``decode_rows_written``
    over an interval: every pool layer, window and global alike, the
    module's too) -> the bytes those calls had to move; two rows written
    are one live row's step in one layer."""
    return (pages_read * page_bytes(cfg, page_size)
            + rows_written * row_bytes(cfg)
            + rows_written / 2 * query_bytes(cfg))


def verify_flops(cfg: dict, pages_read: float, page_size: int) -> float:
    """The two products of two queries a head over those pages."""
    return (pages_read * 2 * 2 * 2 * cfg["num_attention_heads"] * page_size
            * cfg["head_dim"])


def pool_layers(cfg: dict) -> int:
    """Layers that hold pages: the stack's and the module's."""
    return cfg["num_hidden_layers"] + cfg.get("num_nextn_predict_layers", 0)
