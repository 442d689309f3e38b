"""Operations and bytes a step NEEDS of a configuration with Mamba-2 blocks
beside plain GQA attention and un-gated expert blocks (``layer_types``:
"mamba2" | "full_attention" | "moe", one sublayer a block) — from its
widths, the PUBLISHED ones (an expert is 1856 wide whatever the program
stores). Needed, not executed: a row's state is read once and written once
a block and step, an expert is streamed once however many rows chose it, a
boundary copy is no part of a decode step's need. Waste (an idle batch
row's scratch slot, a row padded to a tile, the experts' zero columns) can
only understate a share computed from these, never push it past 100%.

The prefill's operations are those of the CHUNKED (SSD) form at a block of
``serving.chunk_block`` = 128 positions (the published ``chunk_size``):
what the form needs, whatever implements it.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level, ``layer_types`` and the ``serving`` group.
"""
from __future__ import annotations


def _count(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def inner(config: dict, expand: bool = False) -> int:
    """Channels of a Mamba-2 block's input and gate: heads x head_dim (the
    assumption the published size confirms), or ``expand x hidden``."""
    if expand:
        return config["expand"] * config["hidden_size"]
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def conv_channels(config: dict, expand: bool = False) -> int:
    return inner(config, expand) + 2 * config["n_groups"] * config["ssm_state_size"]


def layer_state_bytes(config: dict) -> int:
    """What ONE Mamba-2 block carries for one sequence: a ``[head_dim,
    state]`` matrix a head and the ``taps - 1`` inputs of the convolution."""
    s = config["serving"]
    matrix = inner(config) * config["ssm_state_size"] * s["state_bytes_per_element"]
    conv = ((config["conv_kernel"] - 1) * conv_channels(config)
            * s["conv_state_bytes_per_element"])
    return matrix + conv


def state_bytes(config: dict) -> int:
    """One snapshot: every Mamba-2 block's state."""
    return _count(config, "mamba2") * layer_state_bytes(config)


def kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the cache: K and V of every KV head in every
    attention block."""
    return (_count(config, "full_attention") * 2 * config["num_key_value_heads"]
            * config["head_dim"] * config["serving"]["kv_bytes_per_element"])


def mamba2_params(config: dict, expand: bool = False) -> int:
    """One Mamba-2 block: W_in [H, di + (di + 2 G N) + heads], the depth-wise
    kernel and its bias, dt_bias, A_log and D a head, the gated norm's
    weight [di], W_out [di, H] and the block's pre-norm."""
    h, di, heads = config["hidden_size"], inner(config, expand), config["mamba_num_heads"]
    cc = conv_channels(config, expand)
    return (h * (di + cc + heads) + (config["conv_kernel"] + 1) * cc
            + 3 * heads + di + di * h + h)


def attention_params(config: dict) -> int:
    h, d = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + h


def expert_params(config: dict) -> int:
    """One un-gated expert: W_up and W_down at the published width."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_params(config: dict, experts: int) -> int:
    """One expert block holding ``experts`` routed experts: they, the shared
    expert, the router over ALL experts with its bias, the pre-norm."""
    h = config["hidden_size"]
    router = config["serving"]["router_experts"] if "serving" in config else experts
    return (experts * expert_params(config)
            + 2 * h * config["moe_shared_expert_intermediate_size"]
            + h * router + router + h)


def parameters(config: dict) -> int:
    """Parameters of the configuration as it is run (published widths)."""
    h = config["hidden_size"]
    return (2 * h * config["vocab_size"] + h
            + _count(config, "mamba2") * mamba2_params(config)
            + _count(config, "full_attention") * attention_params(config)
            + _count(config, "moe") * moe_params(config, config["n_routed_experts"]))


def published_parameters(config: dict, expand: bool = False) -> tuple[int, int]:
    """``(total, active a token)`` of the PUBLISHED model, from the file's
    ``published`` group: 31.58 B / 3.23 B with the inner width heads x
    head_dim (``expand`` True: 31.8 B)."""
    pub = config["published"]
    pattern = pub["hybrid_override_pattern"]
    n_m, n_e, n_a = (pattern.count(c) for c in "ME*")
    h, experts = config["hidden_size"], pub["n_routed_experts"]
    whole = dict(config, serving={"router_experts": experts})
    vocab = 2 * h * pub["vocab_size"] + h
    total = (vocab + n_m * mamba2_params(config, expand)
             + n_a * attention_params(config) + n_e * moe_params(whole, experts))
    active = (h * pub["vocab_size"] + h + n_m * mamba2_params(config, expand)
              + n_a * attention_params(config)
              + n_e * moe_params(whole, config["num_experts_per_tok"]))
    return total, active


def ssd_decode_kernel_bytes(config: dict, rows: float) -> float:
    """What ONE launch of ``radixmesh_ssd_decode`` (one block of one step)
    has to move: each of ``rows`` live rows' matrix read and written back,
    and the step's vectors (x in and y out a channel, the decay a head, B
    and C a group), float32."""
    s = config["serving"]
    matrix = inner(config) * config["ssm_state_size"] * s["state_bytes_per_element"]
    vectors = (2 * inner(config) + config["mamba_num_heads"]
               + 2 * config["n_groups"] * config["ssm_state_size"]) * 4
    return rows * (2 * matrix + vectors)


def ssd_decode_bytes_per_step(config: dict, rows: float) -> float:
    """What the Mamba-2 side of ONE decode step has to move: every block's
    weights once, and each live row's state (matrix and convolution inputs)
    read and written back in every block."""
    w = config["serving"]["weight_bytes_per_param"]
    return _count(config, "mamba2") * (
        mamba2_params(config) * w + 2 * rows * layer_state_bytes(config))


def ssd_chunk_flops_per_token(config: dict) -> float:
    """FLOPs one position of ONE Mamba-2 block needs in the chunked form at
    a block of ``L`` = ``serving.chunk_block`` positions: a GROUP and block
    computes ``C B^T`` (2 L^2 N); a head the masked ``(L o C B^T) x`` (2 L^2
    P), the carried state's product ``C S_0`` (2 L N P) and the state's
    update (2 L N P) — a position's share is that over ``L``. The
    projections, the convolution, the gate and the norm are XLA's."""
    L, n = config["serving"]["chunk_block"], config["ssm_state_size"]
    p = config["mamba_head_dim"]
    return float(config["n_groups"] * 2 * L * n
                 + config["mamba_num_heads"] * (2 * L * p + 2 * 2 * n * p))
