"""Serving runtime (SURVEY.md §1 L7-L8, §7.8): frame batcher, middleware
connectors, trainer, and the recognizer service.

The device-collective layer (``parallel``) and this host-transport layer are
deliberately separate (SURVEY.md §5.8): collectives ride ICI inside jitted
graphs; frames and results ride a pluggable ``MiddlewareConnector``.
"""

from opencv_facerecognizer_tpu.runtime.admission import (
    PRIORITY_BULK,
    PRIORITY_INTERACTIVE,
    AdmissionController,
    TokenBucket,
    parse_priority,
)
from opencv_facerecognizer_tpu.runtime.batcher import FrameBatcher
from opencv_facerecognizer_tpu.runtime.connector import (
    FakeConnector,
    JSONLConnector,
    MiddlewareConnector,
)
from opencv_facerecognizer_tpu.runtime.expo import ExpoServer
from opencv_facerecognizer_tpu.runtime.faults import FaultInjector
from opencv_facerecognizer_tpu.runtime.ingest import (
    DecodeWorkerPool,
    IngestConfig,
    IngestPipeline,
    StagingRing,
)
from opencv_facerecognizer_tpu.runtime.journal import DeadLetterJournal
from opencv_facerecognizer_tpu.runtime.recognizer import RecognizerService
from opencv_facerecognizer_tpu.runtime.replication import (
    ReadReplica,
    ReplicaHandle,
    TopicRouter,
    WALTailer,
    WriterLease,
    WriterLeaseHeldError,
)
from opencv_facerecognizer_tpu.runtime.registry import (
    DetectionParity,
    ModelRegistry,
    RegistryStateError,
    RegistrySwapCoordinator,
    registry_params_path,
)
from opencv_facerecognizer_tpu.runtime.rollout import (
    DualScoreParity,
    ReEmbedStage,
    RolloutCoordinator,
    RolloutGateError,
    RolloutStateError,
)
from opencv_facerecognizer_tpu.runtime.resilience import (
    BrownoutPolicy,
    DurabilityDegradedError,
    DurabilityMonitor,
    ResiliencePolicy,
    ServiceSupervisor,
)
from opencv_facerecognizer_tpu.runtime.slo import (
    SLO,
    SLOMonitor,
    default_objectives,
    disk_free_objective,
    link_health_objective,
    loop_liveness_objective,
    registry_parity_objective,
    replication_lag_objective,
    rollout_parity_objective,
)
from opencv_facerecognizer_tpu.runtime.state_store import (
    CheckpointStore,
    EmbedderVersionMismatchError,
    EnrollmentWAL,
    StateLifecycle,
    graceful_shutdown,
)
from opencv_facerecognizer_tpu.runtime.trainer import TheTrainer

__all__ = [
    "AdmissionController",
    "BrownoutPolicy",
    "CheckpointStore",
    "DeadLetterJournal",
    "DecodeWorkerPool",
    "DetectionParity",
    "DualScoreParity",
    "DurabilityDegradedError",
    "DurabilityMonitor",
    "EmbedderVersionMismatchError",
    "EnrollmentWAL",
    "ExpoServer",
    "FakeConnector",
    "FaultInjector",
    "FrameBatcher",
    "IngestConfig",
    "IngestPipeline",
    "JSONLConnector",
    "MiddlewareConnector",
    "ModelRegistry",
    "PRIORITY_BULK",
    "PRIORITY_INTERACTIVE",
    "ReadReplica",
    "ReEmbedStage",
    "RecognizerService",
    "RegistryStateError",
    "RegistrySwapCoordinator",
    "ReplicaHandle",
    "ResiliencePolicy",
    "RolloutCoordinator",
    "RolloutGateError",
    "RolloutStateError",
    "TopicRouter",
    "WALTailer",
    "WriterLease",
    "WriterLeaseHeldError",
    "SLO",
    "SLOMonitor",
    "ServiceSupervisor",
    "StagingRing",
    "default_objectives",
    "disk_free_objective",
    "link_health_objective",
    "loop_liveness_objective",
    "registry_params_path",
    "registry_parity_objective",
    "replication_lag_objective",
    "rollout_parity_objective",
    "StateLifecycle",
    "TheTrainer",
    "TokenBucket",
    "graceful_shutdown",
    "parse_priority",
]
