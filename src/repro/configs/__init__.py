"""Configurations of the placed DLRM recommender (``repro.configs.dlrm``)."""
