"""SPDC core — the paper's contribution as composable JAX modules."""
from .augment import (
    augment,
    augment_block_row,
    augment_for_servers,
    padding_for_servers,
    padding_to_even,
)
from .cipher import (
    CipherMeta,
    cipher,
    cipher_batch,
    cipher_flops,
    equilibrate,
    ewo,
)
from .decipher import Determinant, decipher, decipher_batch, decipher_flops
from .faults import (
    FaultPlan,
    ServerFault,
    apply_faults,
    corrupt_strip,
    normalize_plan,
    resolve_delays,
)
from .inverse import SPDCInverseResult, outsource_inverse
from .keygen import Key, keygen, keygen_batch
from .lu import (
    CommLog,
    det_from_lu,
    lu_block_row,
    lu_blocked,
    lu_diag_factor,
    lu_nserver,
    lu_panel_blocked,
    lu_unblocked,
    nserver_comm_model,
    slogdet_from_lu,
)
from .protocol import (
    SPDCBatchResult,
    SPDCResult,
    common_padded_size,
    outsource_determinant,
    outsource_determinant_mixed,
    resolve_dtype,
)
from .prt import (
    flip_sign,
    growth_safe_sign,
    quantize_seed,
    rot90_cw,
    rotate_degree,
    rotation_sign,
    rotation_sign_paper,
    sign_preserved,
)
from .sdc import checked_matmul, freivalds_residual, sdc_flag
from .seed import Seed, seedgen, seedgen_batch
from .verify import (
    Verdict,
    authenticate,
    epsilon,
    growth_estimate,
    localize,
    per_server_residuals,
    q1,
    q2,
    q3,
    q3_paper_literal,
)

__all__ = [
    "augment", "augment_block_row", "augment_for_servers",
    "padding_for_servers", "padding_to_even",
    "CipherMeta", "cipher", "cipher_batch", "cipher_flops", "equilibrate",
    "ewo",
    "Determinant", "decipher", "decipher_batch", "decipher_flops",
    "FaultPlan", "ServerFault", "apply_faults", "corrupt_strip",
    "normalize_plan", "resolve_delays",
    "Key", "keygen", "keygen_batch",
    "SPDCInverseResult", "outsource_inverse",
    "CommLog", "det_from_lu", "lu_block_row", "lu_blocked", "lu_diag_factor",
    "lu_nserver", "lu_panel_blocked", "lu_unblocked", "nserver_comm_model",
    "slogdet_from_lu",
    "SPDCBatchResult", "SPDCResult", "common_padded_size",
    "outsource_determinant", "outsource_determinant_mixed", "resolve_dtype",
    "flip_sign", "growth_safe_sign",
    "quantize_seed", "rot90_cw", "rotate_degree", "rotation_sign",
    "rotation_sign_paper", "sign_preserved",
    "checked_matmul", "freivalds_residual", "sdc_flag",
    "Seed", "seedgen", "seedgen_batch",
    "Verdict", "authenticate", "epsilon", "growth_estimate", "localize",
    "per_server_residuals",
    "q1", "q2", "q3", "q3_paper_literal",
]
