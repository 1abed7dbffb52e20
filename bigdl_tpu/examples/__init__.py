"""Example programs (reference example/ — SURVEY §1.8): pretrained-model
validation, GloVe-CNN text classification, UDF-style serving, ML
pipelines, TF load/save, image prediction, train-to-accuracy proofs."""

