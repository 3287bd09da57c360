"""The paper's energy model (Sec. 5.2, Table 1), written out for the
reference: E = P * S / B per transfer, battery endpoints only.

Conventions: 4G and NB-IoT go through infrastructure (one tx + one rx per
unicast; the mains-powered edge server's side is free). 802.11 runs as a
WiFi-Direct star whose access point is the largest mule: a unicast
between two non-AP mules is relayed (2 tx + 2 rx), one with the AP as an
endpoint is direct. Observations are 433 B, models 1540 B, index and
centre-id messages 8 B. The program keeps its ledger on the host in
float64, so the reference and its control both do.
"""
from __future__ import annotations

from typing import Dict, Tuple

OBS_BYTES = 54 * 8 + 1
MODEL_BYTES = 55 * 7 * 4
INDEX_BYTES = 8

# tech: (tx mW, up Mbit/s, rx mW, down Mbit/s), the paper's Table 1
TECHS: Dict[str, Tuple[float, float, float, float]] = {
    "4g": (2100.0, 75.0, 2100.0, 35.0),
    "nbiot": (199.0, 0.2, 199.52, 0.2),
    "802.15.4": (3.0, 0.12, 3.0, 0.12),
    "wifi": (1080.0, 48.0, 740.0, 48.0),
}
RELAYED = {"wifi"}          # star through an access point


def transfer_mj(tech: str, nbytes: float, n_tx: int, n_rx: int) -> float:
    tx_mw, up, rx_mw, down = TECHS[tech]
    tx = tx_mw * (nbytes * 8.0 / (up * 1e6))
    rx = rx_mw * (nbytes * 8.0 / (down * 1e6))
    return float(n_tx * tx + n_rx * rx)


def unicast_counts(tech: str, src_es: bool, dst_es: bool, src_ap: bool,
                   dst_ap: bool) -> Tuple[int, int]:
    if tech not in RELAYED or src_es or dst_es:
        return (0 if src_es else 1), (0 if dst_es else 1)
    hops = 1 if (src_ap or dst_ap) else 2
    return hops, hops

