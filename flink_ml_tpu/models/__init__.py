"""L4 — the algorithm library.

Reference: ``flink-ml-lib`` (48 Stage implementations, SURVEY.md §2.5). Mirrors the
reference's package-per-group layout: ``classification``, ``clustering``, ``feature``,
``regression``, ``evaluation``, ``stats``, ``recommendation``.

``STAGE_REGISTRY`` maps public stage name → dotted class path. It is the single
source of truth for persistence dispatch and for the completeness test (the analogue
of the reference's ``test_ml_lib_completeness.py:31``): every stage the framework
claims must be importable from here.
"""
import importlib

STAGE_REGISTRY = {
    # classification
    "LogisticRegression": "flink_ml_tpu.models.classification.logistic_regression.LogisticRegression",
    "LogisticRegressionModel": "flink_ml_tpu.models.classification.logistic_regression.LogisticRegressionModel",
    "LinearSVC": "flink_ml_tpu.models.classification.linearsvc.LinearSVC",
    "LinearSVCModel": "flink_ml_tpu.models.classification.linearsvc.LinearSVCModel",
    "MLPClassifier": "flink_ml_tpu.models.classification.mlp_classifier.MLPClassifier",
    "MLPClassifierModel": "flink_ml_tpu.models.classification.mlp_classifier.MLPClassifierModel",
    "NaiveBayes": "flink_ml_tpu.models.classification.naive_bayes.NaiveBayes",
    "NaiveBayesModel": "flink_ml_tpu.models.classification.naive_bayes.NaiveBayesModel",
    "Knn": "flink_ml_tpu.models.classification.knn.Knn",
    "KnnModel": "flink_ml_tpu.models.classification.knn.KnnModel",
    "OnlineLogisticRegression": "flink_ml_tpu.models.classification.online_logistic_regression.OnlineLogisticRegression",
    "OnlineLogisticRegressionModel": "flink_ml_tpu.models.classification.online_logistic_regression.OnlineLogisticRegressionModel",
    "SelfAttentionClassifier": "flink_ml_tpu.models.classification.attention_classifier.SelfAttentionClassifier",
    "SelfAttentionClassifierModel": "flink_ml_tpu.models.classification.attention_classifier.SelfAttentionClassifierModel",
    # language models
    "DecoderLM": "flink_ml_tpu.models.lm.decoder_lm.DecoderLM",
    "DecoderLMModel": "flink_ml_tpu.models.lm.decoder_lm.DecoderLMModel",
    # clustering
    "KMeans": "flink_ml_tpu.models.clustering.kmeans.KMeans",
    "KMeansModel": "flink_ml_tpu.models.clustering.kmeans.KMeansModel",
    "OnlineKMeans": "flink_ml_tpu.models.clustering.online_kmeans.OnlineKMeans",
    "OnlineKMeansModel": "flink_ml_tpu.models.clustering.online_kmeans.OnlineKMeansModel",
    "AgglomerativeClustering": "flink_ml_tpu.models.clustering.agglomerative_clustering.AgglomerativeClustering",
    # evaluation / stats / recommendation
    "BinaryClassificationEvaluator": "flink_ml_tpu.models.evaluation.binary_classification_evaluator.BinaryClassificationEvaluator",
    "ChiSqTest": "flink_ml_tpu.models.stats.tests.ChiSqTest",
    "ANOVATest": "flink_ml_tpu.models.stats.tests.ANOVATest",
    "FValueTest": "flink_ml_tpu.models.stats.tests.FValueTest",
    "Swing": "flink_ml_tpu.models.recommendation.swing.Swing",
    # feature (stateless)
    "Binarizer": "flink_ml_tpu.models.feature.binarizer.Binarizer",
    "Bucketizer": "flink_ml_tpu.models.feature.bucketizer.Bucketizer",
    "DCT": "flink_ml_tpu.models.feature.dct.DCT",
    "ElementwiseProduct": "flink_ml_tpu.models.feature.elementwise_product.ElementwiseProduct",
    "FeatureHasher": "flink_ml_tpu.models.feature.feature_hasher.FeatureHasher",
    "HashingTF": "flink_ml_tpu.models.feature.hashing_tf.HashingTF",
    "Interaction": "flink_ml_tpu.models.feature.interaction.Interaction",
    "NGram": "flink_ml_tpu.models.feature.ngram.NGram",
    "Normalizer": "flink_ml_tpu.models.feature.normalizer.Normalizer",
    "PolynomialExpansion": "flink_ml_tpu.models.feature.polynomial_expansion.PolynomialExpansion",
    "RandomSplitter": "flink_ml_tpu.models.feature.random_splitter.RandomSplitter",
    "RegexTokenizer": "flink_ml_tpu.models.feature.tokenizer.RegexTokenizer",
    "SQLTransformer": "flink_ml_tpu.models.feature.sql_transformer.SQLTransformer",
    "StopWordsRemover": "flink_ml_tpu.models.feature.stop_words_remover.StopWordsRemover",
    "Tokenizer": "flink_ml_tpu.models.feature.tokenizer.Tokenizer",
    "VectorAssembler": "flink_ml_tpu.models.feature.vector_assembler.VectorAssembler",
    "VectorSlicer": "flink_ml_tpu.models.feature.vector_slicer.VectorSlicer",
    # feature (fitted)
    "CountVectorizer": "flink_ml_tpu.models.feature.count_vectorizer.CountVectorizer",
    "CountVectorizerModel": "flink_ml_tpu.models.feature.count_vectorizer.CountVectorizerModel",
    "IDF": "flink_ml_tpu.models.feature.idf.IDF",
    "IDFModel": "flink_ml_tpu.models.feature.idf.IDFModel",
    "Imputer": "flink_ml_tpu.models.feature.imputer.Imputer",
    "ImputerModel": "flink_ml_tpu.models.feature.imputer.ImputerModel",
    "IndexToStringModel": "flink_ml_tpu.models.feature.string_indexer.IndexToStringModel",
    "KBinsDiscretizer": "flink_ml_tpu.models.feature.kbins_discretizer.KBinsDiscretizer",
    "KBinsDiscretizerModel": "flink_ml_tpu.models.feature.kbins_discretizer.KBinsDiscretizerModel",
    "MaxAbsScaler": "flink_ml_tpu.models.feature.scalers.MaxAbsScaler",
    "MaxAbsScalerModel": "flink_ml_tpu.models.feature.scalers.MaxAbsScalerModel",
    "MinHashLSH": "flink_ml_tpu.models.feature.lsh.MinHashLSH",
    "MinHashLSHModel": "flink_ml_tpu.models.feature.lsh.MinHashLSHModel",
    "MinMaxScaler": "flink_ml_tpu.models.feature.scalers.MinMaxScaler",
    "MinMaxScalerModel": "flink_ml_tpu.models.feature.scalers.MinMaxScalerModel",
    "OneHotEncoder": "flink_ml_tpu.models.feature.one_hot_encoder.OneHotEncoder",
    "OneHotEncoderModel": "flink_ml_tpu.models.feature.one_hot_encoder.OneHotEncoderModel",
    "RobustScaler": "flink_ml_tpu.models.feature.scalers.RobustScaler",
    "RobustScalerModel": "flink_ml_tpu.models.feature.scalers.RobustScalerModel",
    "StringIndexer": "flink_ml_tpu.models.feature.string_indexer.StringIndexer",
    "StringIndexerModel": "flink_ml_tpu.models.feature.string_indexer.StringIndexerModel",
    "UnivariateFeatureSelector": "flink_ml_tpu.models.feature.univariate_feature_selector.UnivariateFeatureSelector",
    "UnivariateFeatureSelectorModel": "flink_ml_tpu.models.feature.univariate_feature_selector.UnivariateFeatureSelectorModel",
    "VarianceThresholdSelector": "flink_ml_tpu.models.feature.variance_threshold_selector.VarianceThresholdSelector",
    "VarianceThresholdSelectorModel": "flink_ml_tpu.models.feature.variance_threshold_selector.VarianceThresholdSelectorModel",
    "VectorIndexer": "flink_ml_tpu.models.feature.vector_indexer.VectorIndexer",
    "VectorIndexerModel": "flink_ml_tpu.models.feature.vector_indexer.VectorIndexerModel",
    "StandardScaler": "flink_ml_tpu.models.feature.standard_scaler.StandardScaler",
    "StandardScalerModel": "flink_ml_tpu.models.feature.standard_scaler.StandardScalerModel",
    "OnlineStandardScaler": "flink_ml_tpu.models.feature.standard_scaler.OnlineStandardScaler",
    "OnlineStandardScalerModel": "flink_ml_tpu.models.feature.standard_scaler.OnlineStandardScalerModel",
    # regression
    "LinearRegression": "flink_ml_tpu.models.regression.linear_regression.LinearRegression",
    "LinearRegressionModel": "flink_ml_tpu.models.regression.linear_regression.LinearRegressionModel",
}


def get_stage_class(name: str):
    dotted = STAGE_REGISTRY[name]
    module_name, _, cls_name = dotted.rpartition(".")
    return getattr(importlib.import_module(module_name), cls_name)
