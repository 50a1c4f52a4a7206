from repro.serve.counterfactual import (CounterfactualService, ServiceAnswer,
                                        Ticket)

__all__ = ["CounterfactualService", "ServiceAnswer", "Ticket"]
