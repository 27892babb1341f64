from repro.fl.fl_model import MODELS, accuracy, masked_loss, mlr_init, mlp_init
from repro.fl.training import FederatedTrainer, TrainHistory, train_federated
from repro.fl.live import (DEFAULT_CHURN, POLICIES, LiveHFELRunner,
                           LiveHistory, churn_tick, run_live)

__all__ = ["MODELS", "accuracy", "masked_loss", "mlr_init", "mlp_init",
           "FederatedTrainer", "TrainHistory", "train_federated",
           "DEFAULT_CHURN", "POLICIES", "LiveHFELRunner", "LiveHistory",
           "churn_tick", "run_live"]
