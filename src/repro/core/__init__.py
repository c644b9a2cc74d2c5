"""Lilac-TM core: locality-aware lease-based replicated transactional memory.

The paper's primary contribution (Hendler et al., 2013) as a composable
library:

* fine-grained lease management (:mod:`repro.core.lease`, Algorithm 1) and
  the coarse-grained ALC baseline;
* the Distributed Transaction Dispatcher ILP with short-/long-term policies
  (:mod:`repro.core.dtd`, vectorized in JAX);
* the Transaction Forwarder protocol (:mod:`repro.core.forwarder`);
* a TL2-style local STM with batched JAX certification (:mod:`repro.core.stm`);
* a simulated view-synchronous GCS (:mod:`repro.core.gcs`) and the
  discrete-event cluster simulator (:mod:`repro.core.cluster`) that together
  reproduce the paper's evaluation.
"""
from .conflict import ConflictClassMap
from .cluster import Cluster, Metrics, SimConfig, TxnSpec, Workload
from .dtd import DTD, DTDConfig, C_AB, C_P2P, C_URB
from .events import EventQueue
from .forwarder import CommitNotice, ForwardPolicy, ForwardRequest
from .gcs import GCSLatency, SimGCS
from .lease import ALCLeaseManager, FGLLeaseManager, LeaseRequest, LOR
from .stats import CpuMeter, DecayedFrequency
from .stm import Transaction, VersionedStore, validate_batch
from .workloads import BankWorkload, TpccConflictMap, TpccLayout, TpccWorkload

ALGORITHMS = {
    # paper variant -> (lease_kind, dtd policy)
    "ALC": ("alc", "local"),
    "FGL": ("fgl", "local"),
    "MG-ALC": ("alc", "opt"),
    "LILAC-TM-ST": ("fgl", "short"),
    "LILAC-TM-LT": ("fgl", "long"),
    "LILAC-TM-OPT": ("fgl", "opt"),
}


def make_cluster(algorithm: str, workload, cfg: SimConfig = None, ccmap=None, **overrides):
    """Build a cluster configured for one of the paper's algorithm variants."""
    from dataclasses import replace

    lease_kind, policy = ALGORITHMS[algorithm]
    cfg = cfg or SimConfig()
    dtd = replace(cfg.dtd, policy=policy)
    cfg = replace(cfg, lease_kind=lease_kind, dtd=dtd, **overrides)
    return Cluster(cfg, workload, ccmap=ccmap)
